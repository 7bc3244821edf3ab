"""Compression pipeline: rotated-space quantized exchange + backend registry.

This is the subsystem behind every production use of the position-aware
lattice quantizer. It has two layers:

**Backend registry** — the four primitive ops of the exchange (batched
randomized-Hadamard ``rotate``, fused rotate+stochastic-round+wrap
``encode``, rotated-space positional ``snap``, and the fully fused
``decode``) exist in three interchangeable implementations:

  * ``"jnp"``             — pure-jnp einsum composition (XLA fuses what it
                            can; the CPU-CI default),
  * ``"pallas_interpret"`` — the Pallas kernels from ``repro.kernels.
                            exchange`` run through the interpreter, so CPU CI
                            validates the exact code path a TPU executes,
  * ``"pallas"``          — the same kernels compiled for a real TPU.

Select per experiment with ``FedConfig.kernel_backend``; all backends share
one ``gamma`` derivation so messages are interchangeable across them.

**Rotated-space exchange** (``ExchangePipeline.quafl_round``) — the QuAFL
round restructured so every vector is rotated at most once. All messages in
a round share one rotation key (the paper already assumes shared
per-interaction keys; sharing across the round's messages is equally valid
because the rotation is orthogonal), so encode/decode/averaging all happen
in rotated coordinates and only the final server/client states are
inverse-rotated. Per round with ``s`` sampled clients this costs exactly

  * ``s + 1`` forward rotations  — the s client messages (fused with their
    encode) and the server's rotation (the uplink decode reference). The
    server's own Enc(X_t) needs no rotation pass: its γ depends on the
    decoded uplink so it cannot fold into the srv_rot pass, but the cached
    rotated coords make it a pure elementwise quantize
    (``Backend.quantize`` — stochastic round + wrap, no Hadamard work),
  * ``s + 1`` inverse rotations — the s new client states + the new server
    state, rotated back only after averaging,

down from the seed composition's ``5s + 1`` full-model rotation passes (and
the first fused version's ``s + 2`` forward). A trace-time counter
(:class:`repro.analysis.opbudget.OpBudget`, exposed as ``pipeline.stats``)
audits this invariant in the tests and the ``repro.analysis.lint`` gate.

The downlink decode reference is the client's **current** model Y^i (the
model it holds when the reply arrives) rather than its pre-round state X^i;
both satisfy the Lemma 3.1 wrap condition and Y^i is already resident in
rotated space, which is what removes the per-client reference rotations.

``quafl_round_reference`` is the materialize-everything per-message
composition over the *same* key/noise/γ derivation — the equivalence oracle
for the fused path (tests assert fp32-level agreement on full rounds).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.provenance import wire_mark
from repro.compression.rotation import (DEFAULT_BLOCK, _signs, dither,
                                        hadamard_matrix, pad_len)
from repro.kernels.exchange import (block_geometry, fused_decode,
                                    fused_encode, fused_rotate, pack_codes,
                                    quantize_codes, snap_codes, unpack_codes)

BACKENDS = ("jnp", "pallas_interpret", "pallas")


class LatticeWire(NamedTuple):
    """Per-direction wire parametrization of the lattice exchange.

    ``bits`` is the static bit-width (kernel wrap/pack parameter);
    ``pack = 8 // bits`` ships that many codes per byte (the
    ``lattice_packed`` codec; 1 = historical unpacked layout); ``levels``
    optionally carries PER-MESSAGE quantization levels (a (m,) f32 array of
    powers of two <= 2^bits) for heterogeneous per-client bit budgets —
    supported by every backend: the Pallas kernels take the moduli as a
    per-message SMEM operand riding next to the γ scalars.
    """
    bits: int
    pack: int = 1
    levels: Any = None

def wire_container_dtype(wire: LatticeWire):
    """The uint dtype one wire code physically ships in (packed wires hold
    ``pack`` codes per uint8 byte)."""
    if wire.pack > 1 or wire.bits <= 8:
        return jnp.uint8
    return jnp.uint16 if wire.bits <= 16 else jnp.uint32


def observe_lattice_wire(codes, gammas, wire: LatticeWire, channel: str):
    """Record the wire form of a lattice message batch for the wire-truth
    audit: dead-code casts + identity marks that XLA eliminates, but that
    stay visible in the traced jaxpr. The leading axis is the message
    batch."""
    d = int(codes.shape[-1]) * max(int(wire.pack), 1)
    wire_mark(codes.astype(wire_container_dtype(wire)), channel=channel,
              part="codes", codec="wire", batched=True, d=d)
    wire_mark(jnp.asarray(gammas, jnp.float32).reshape(-1), channel=channel,
              part="gamma", codec="wire", batched=True, d=d)
    if wire.levels is not None:
        wire_mark(jnp.asarray(wire.levels, jnp.float32).reshape(-1),
                  channel=channel, part="levels", codec="wire", batched=True,
                  d=d)


# fp32 precision floor: the modulo decode needs y/γ (and w/γ) to keep
# sub-integer precision, so γ must not drop below max|rot(x)|·2^-18. The
# fused encode needs γ BEFORE the rotation runs, so we bound max|rot(x)|
# by the same subgaussian coordinate estimate the wrap window uses
# (rotated coordinates have scale ‖x‖/sqrt(d_pad)) — the floor keeps the
# seed's ~‖x‖·polylog/sqrt(d)·2^-18 scale instead of a loose ‖x‖·2^-18.
GAMMA_NORM_FLOOR = 2.0 ** -18


# ---------------------------------------------------------------------------
# shared gamma derivation (identical across backends)
# ---------------------------------------------------------------------------

def coord_bound(norms, d_pad: int):
    """High-probability bound on the max rotated coordinate of a vector
    with the given l2 norm (subgaussian scale norm/sqrt(d_pad))."""
    return (jnp.asarray(norms, jnp.float32) / np.sqrt(d_pad)
            * (np.sqrt(2 * np.log(2 * d_pad + 1)) + 2.0))


def wrap_gamma(dist_hint, d: int, *, bits: int = None, levels=None,
               block: int = DEFAULT_BLOCK, safety: float = 8.0):
    """Per-message lattice scale from the encoder-local distance hint.

    After rotation the difference coordinates are subgaussian with scale
    dist/sqrt(d_pad); the wrap window 2^b·γ must exceed twice the max
    coordinate. Vectorized over ``dist_hint``; ``levels`` (scalar or a
    per-message array, default ``1 << bits``) supports heterogeneous
    bit-widths within one batched call.
    """
    if levels is None:
        levels = 1 << bits
    d_pad = pad_len(d, block)
    gamma = safety * 2.0 * coord_bound(dist_hint, d_pad) / levels
    return jnp.maximum(gamma, 1e-12)


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

class Backend(NamedTuple):
    """The five primitive ops; every op is batched over a message axis.

    The quantizing ops additionally take ``pack`` (sub-byte packed codes,
    :mod:`repro.kernels.exchange` layout) and ``levels2`` (optional
    per-message quantization levels for heterogeneous bit budgets — on the
    Pallas backends the moduli ride as a per-message SMEM operand).
    """
    name: str
    rotate: Callable    # (x2, signs, *, block, inverse) -> y2
    encode: Callable    # (x2, signs, u2, gammas, *, bits, block,
                        #  want_rotated, pack, levels2)
                        #  -> codes | (rotated, codes)
    quantize: Callable  # (y2_rotated, u2, gammas, *, bits, block, pack,
                        #  levels2) -> codes
    snap: Callable      # (codes2, wrot2, gammas, *, bits, block, pack,
                        #  levels2) -> q2
    decode: Callable    # (codes2, ref2, signs, gammas, *, bits, block,
                        #  pack, levels2) -> x2


def _levels_jnp(bits, levels2):
    """The wrap modulus: the static 2^bits, or per-message (m, 1) rows."""
    if levels2 is None:
        return 1 << bits
    return jnp.asarray(levels2, jnp.float32).reshape(-1, 1)


def _rotate_jnp(x2, signs, *, block=DEFAULT_BLOCK, inverse=False):
    m, d_pad = x2.shape
    b, _, r, c, nb = block_geometry(d_pad, block)
    hr = jnp.asarray(hadamard_matrix(r))
    hc = jnp.asarray(hadamard_matrix(c))
    x = x2.astype(jnp.float32)
    if not inverse:
        x = x * signs[None, :]
    y = jnp.einsum("ij,bjk,kl->bil", hr, x.reshape(m * nb, r, c), hc,
                   precision=jax.lax.Precision.HIGHEST) * (1.0 / np.sqrt(b))
    y = y.reshape(m, d_pad)
    if inverse:
        y = y * signs[None, :]
    return y


def _encode_jnp(x2, signs, u2, gammas, *, bits=8, block=DEFAULT_BLOCK,
                want_rotated=False, pack=1, levels2=None):
    y = _rotate_jnp(x2, signs, block=block)
    g = jnp.asarray(gammas, jnp.float32).reshape(-1, 1)
    codes = jnp.mod(jnp.floor(y / g + u2),
                    _levels_jnp(bits, levels2)).astype(jnp.uint32)
    if pack > 1:
        codes = pack_codes(codes, bits=bits, block=block)
    return (y, codes) if want_rotated else codes


def _quantize_jnp(y2, u2, gammas, *, bits=8, block=DEFAULT_BLOCK, pack=1,
                  levels2=None):
    g = jnp.asarray(gammas, jnp.float32).reshape(-1, 1)
    codes = jnp.mod(jnp.floor(y2.astype(jnp.float32) / g + u2),
                    _levels_jnp(bits, levels2)).astype(jnp.uint32)
    if pack > 1:
        codes = pack_codes(codes, bits=bits, block=block)
    return codes


def _snap_jnp(codes2, wrot2, gammas, *, bits=8, block=DEFAULT_BLOCK, pack=1,
              levels2=None):
    if pack > 1:
        codes2 = unpack_codes(codes2, bits=bits, block=block)
    levels = _levels_jnp(bits, levels2)
    cc = codes2.astype(jnp.float32)
    g = jnp.asarray(gammas, jnp.float32).reshape(-1, 1)
    q = cc + levels * jnp.round((wrot2 / g - cc) / levels)
    return q * g


def _decode_jnp(codes2, ref2, signs, gammas, *, bits=8, block=DEFAULT_BLOCK,
                pack=1, levels2=None):
    w = _rotate_jnp(ref2, signs, block=block)
    xr = _snap_jnp(codes2, w, gammas, bits=bits, block=block, pack=pack,
                   levels2=levels2)
    return _rotate_jnp(xr, signs, block=block, inverse=True)


def _pallas_backend(name: str, interpret: bool) -> Backend:
    return Backend(
        name=name,
        rotate=partial(fused_rotate, interpret=interpret),
        encode=partial(fused_encode, interpret=interpret),
        quantize=partial(quantize_codes, interpret=interpret),
        snap=partial(snap_codes, interpret=interpret),
        decode=partial(fused_decode, interpret=interpret),
    )


_REGISTRY = {
    "jnp": Backend("jnp", _rotate_jnp, _encode_jnp, _quantize_jnp, _snap_jnp,
                   _decode_jnp),
    "pallas_interpret": _pallas_backend("pallas_interpret", interpret=True),
    "pallas": _pallas_backend("pallas", interpret=False),
}


def get_backend(name: str) -> Backend:
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown kernel backend {name!r}; choose from {BACKENDS}")
    return _REGISTRY[name]


# ---------------------------------------------------------------------------
# rotation audit counter (trace-time: counts are structural, not data-dep.)
# ---------------------------------------------------------------------------
# The counter class itself lives in repro.analysis.opbudget (promoted from
# the bespoke RotationStats that used to be defined here); the pipeline
# keeps incrementing ``self.stats.fwd`` / ``.inv`` at trace time and the
# analyzer audits the counts against the declared budget.


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------

@dataclass(eq=False)
class ExchangePipeline:
    """Rotated-space quantized-exchange engine over a selectable backend."""
    bits: int = 8
    block: int = DEFAULT_BLOCK
    backend: str = "jnp"
    safety: float = 8.0

    def __post_init__(self):
        from repro.analysis.opbudget import OpBudget
        self.ops = get_backend(self.backend)
        self.stats = OpBudget()

    # -- helpers ------------------------------------------------------------
    def _pad(self, x2):
        d = x2.shape[-1]
        d_pad = pad_len(d, self.block)
        if d_pad == d:
            return x2.astype(jnp.float32)
        return jnp.pad(x2.astype(jnp.float32),
                       ((0, 0), (0, d_pad - d)))

    def signs_for(self, krot, d: int):
        return _signs(krot, pad_len(d, self.block))

    def _wire(self, wire: LatticeWire) -> LatticeWire:
        return wire if wire is not None else LatticeWire(self.bits)

    def gammas(self, dist_hints, xnorms, d: int, wire: LatticeWire = None):
        """Wrap-window γ from the distance hint, floored at the fp32
        precision limit of the message's own rotated coordinates (estimated
        pre-rotation from ‖x‖ so it fuses with the encode kernel)."""
        wire = self._wire(wire)
        base = wrap_gamma(dist_hints, d, bits=wire.bits, levels=wire.levels,
                          block=self.block, safety=self.safety)
        floor = coord_bound(xnorms, pad_len(d, self.block)) * GAMMA_NORM_FLOOR
        return jnp.maximum(base, floor)

    # -- counted primitive ops (inputs (m, d) original / (m, d_pad) rotated)
    def rotate(self, x2, signs):
        self.stats.fwd += int(x2.shape[0])
        return self.ops.rotate(self._pad(x2), signs, block=self.block)

    def rotate_encode(self, x2, signs, u2, gammas, *, want_rotated=True,
                      wire: LatticeWire = None):
        wire = self._wire(wire)
        self.stats.fwd += int(x2.shape[0])
        return self.ops.encode(self._pad(x2), signs, u2, gammas,
                               bits=wire.bits, block=self.block,
                               want_rotated=want_rotated, pack=wire.pack,
                               levels2=wire.levels)

    def quantize(self, y2_rot, u2, gammas, wire: LatticeWire = None):
        """Elementwise encode of ALREADY-ROTATED coords — no rotation pass
        (and no ``stats.fwd`` increment): stochastic round + wrap only."""
        wire = self._wire(wire)
        return self.ops.quantize(y2_rot, u2, gammas, bits=wire.bits,
                                 block=self.block, pack=wire.pack,
                                 levels2=wire.levels)

    def snap(self, codes2, wrot2, gammas, wire: LatticeWire = None):
        wire = self._wire(wire)
        return self.ops.snap(codes2, wrot2, gammas, bits=wire.bits,
                             block=self.block, pack=wire.pack,
                             levels2=wire.levels)

    def unrotate(self, y2, signs, d: int):
        self.stats.inv += int(y2.shape[0])
        return self.ops.rotate(y2, signs, block=self.block,
                               inverse=True)[:, :d]

    def decode(self, codes2, ref2, signs, gammas, d: int,
               wire: LatticeWire = None):
        """Full fused Dec(ref, msg): rotate ref + snap + inverse rotate."""
        wire = self._wire(wire)
        m = max(codes2.shape[0], ref2.shape[0])
        self.stats.fwd += int(ref2.shape[0])
        self.stats.inv += m
        return self.ops.decode(codes2, self._pad(ref2), signs, gammas,
                               bits=wire.bits, block=self.block,
                               pack=wire.pack, levels2=wire.levels)[:, :d]

    # -- per-round key/noise derivation (shared with the reference path) ----
    def _round_randomness(self, key, s: int, d: int):
        d_pad = pad_len(d, self.block)
        signs = self.signs_for(jax.random.fold_in(key, 0), d)
        u_srv = dither(jax.random.fold_in(key, 1), (1, d_pad))
        k_cl = jax.random.split(jax.random.fold_in(key, 2), s)
        u_cl = jax.vmap(lambda k: dither(k, (d_pad,)))(k_cl)
        return signs, u_cl, u_srv

    # ------------------------------------------------------------------
    # one full QuAFL exchange, entirely in rotated coordinates
    # ------------------------------------------------------------------
    def quafl_round(self, key, server, Y, hints_up, *, avg_mode="both",
                    up: LatticeWire = None, down: LatticeWire = None):
        """Quantized exchange + (s+1)-averaging of one server round.

        server: (d,) X_t; Y: (s, d) client models at poll time; hints_up:
        (s,) upper estimates of ‖Y^i − X_t‖. ``up`` / ``down`` select the
        per-direction wire format (bit-width, sub-byte packing, optional
        per-message levels for heterogeneous client bit budgets); both
        default to this pipeline's uniform ``bits``. Returns (server_new
        (d,), clients_new (s, d), hint_srv, rel_err) — hint_srv is the
        downlink wrap hint (feeds ``srv_dist_est``), rel_err the mean
        relative quantization error of the uplink.
        """
        s, d = Y.shape
        up, down = self._wire(up), self._wire(down)
        signs, u_cl, u_srv = self._round_randomness(key, s, d)

        # uplink: fused rotate+encode of every client message; the rotated
        # coords come back for free and serve as downlink decode references.
        gam_up = self.gammas(hints_up, jnp.linalg.norm(Y, axis=1), d, up)
        Y_rot, codes_up = self.rotate_encode(Y, signs, u_cl, gam_up, wire=up)
        observe_lattice_wire(codes_up, gam_up, up, channel="up")
        srv_rot = self.rotate(server[None], signs)
        QY_rot = self.snap(codes_up, srv_rot, gam_up, up)      # (s, d_pad)

        # downlink: the server's γ depends on the decoded uplink, so its
        # encode cannot fold into the srv_rot pass above — but rot(X_t) is
        # already cached in ``srv_rot``, so Enc(X_t) is a pure elementwise
        # quantize of the cached coords (no second rotation pass; the round
        # budget is s+1 forward rotations, down from s+2).
        hint_srv = jnp.max(jnp.linalg.norm(QY_rot - srv_rot, axis=1)) + 1e-8
        gam_dn = self.gammas(hint_srv[None], jnp.linalg.norm(server)[None],
                             d, down)
        codes_dn = self.quantize(srv_rot, u_srv, gam_dn, down)
        observe_lattice_wire(codes_dn, gam_dn, down, channel="down")
        QX_rot = self.snap(codes_dn, Y_rot, gam_dn, down)      # (s, d_pad)

        # (s+1)-averaging in rotated coordinates; inverse-rotate only the
        # final states.
        if avg_mode in ("both", "server_only"):
            srv_new_rot = (srv_rot[0] + jnp.sum(QY_rot, 0)) / (s + 1)
        else:
            srv_new_rot = jnp.mean(QY_rot, 0)
        if avg_mode in ("both", "client_only"):
            cl_new_rot = QX_rot / (s + 1) + s * Y_rot / (s + 1)
        else:
            cl_new_rot = QX_rot
        server_new = self.unrotate(srv_new_rot[None], signs, d)[0]
        clients_new = self.unrotate(cl_new_rot, signs, d)

        rel_err = jnp.mean(jnp.linalg.norm(QY_rot - Y_rot, axis=1)
                           / (jnp.linalg.norm(Y_rot, axis=1) + 1e-9))
        return server_new, clients_new, hint_srv, rel_err

    # ------------------------------------------------------------------
    # equivalence oracle: per-message materialize-everything composition
    # ------------------------------------------------------------------
    def quafl_round_reference(self, key, server, Y, hints_up, *,
                              avg_mode="both", up: LatticeWire = None,
                              down: LatticeWire = None):
        """Same exchange over the same keys/noise/γ, composed message by
        message in original coordinates (the seed's structure). Used by the
        tests to pin the rotated-space path; O(s) extra rotation passes."""
        s, d = Y.shape
        up, down = self._wire(up), self._wire(down)
        signs, u_cl, u_srv = self._round_randomness(key, s, d)
        rot = partial(_rotate_jnp, block=self.block)
        unrot = partial(_rotate_jnp, block=self.block, inverse=True)

        gam_up = self.gammas(hints_up, jnp.linalg.norm(Y, axis=1), d, up)
        Yp = self._pad(Y)
        srvp = self._pad(server[None])
        codes_up = _encode_jnp(Yp, signs, u_cl, gam_up, bits=up.bits,
                               block=self.block, pack=up.pack,
                               levels2=up.levels)
        # each message decoded separately against the server (full rotate /
        # snap / inverse-rotate per message), back in original space
        QY = unrot(_snap_jnp(codes_up, rot(srvp, signs), gam_up,
                             bits=up.bits, block=self.block, pack=up.pack,
                             levels2=up.levels), signs)
        hint_srv = jnp.max(jnp.linalg.norm(QY - srvp, axis=1)) + 1e-8
        gam_dn = self.gammas(hint_srv[None], jnp.linalg.norm(server)[None],
                             d, down)
        codes_dn = _encode_jnp(srvp, signs, u_srv, gam_dn, bits=down.bits,
                               block=self.block, pack=down.pack,
                               levels2=down.levels)
        QX = unrot(_snap_jnp(codes_dn, rot(Yp, signs), gam_dn,
                             bits=down.bits, block=self.block,
                             pack=down.pack, levels2=down.levels), signs)

        if avg_mode in ("both", "server_only"):
            srv_new = (srvp[0] + jnp.sum(QY, 0)) / (s + 1)
        else:
            srv_new = jnp.mean(QY, 0)
        if avg_mode in ("both", "client_only"):
            cl_new = QX / (s + 1) + s * Yp / (s + 1)
        else:
            cl_new = QX
        rel_err = jnp.mean(jnp.linalg.norm(QY - Yp, axis=1)
                           / (jnp.linalg.norm(Yp, axis=1) + 1e-9))
        return srv_new[:d], cl_new[:, :d], hint_srv, rel_err
