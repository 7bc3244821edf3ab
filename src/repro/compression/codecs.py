"""Composable codec API: pluggable compression, per direction, per client.

The paper composes three system relaxations — data heterogeneity,
asynchrony, and compression — but a compression scheme is ONE point in a
large design space (lattice vs. scalar stochastic rounding vs.
sparsification; 1..32 bits; packed vs. word-aligned wire formats). This
module makes the scheme a first-class, registry-selected object so every
algorithm in :mod:`repro.fed` takes ``uplink=`` / ``downlink=`` codec specs
instead of hard-wiring one quantizer:

**Codec protocol** — ``encode(key, x, hint) -> msg``,
``decode(key, msg, ref) -> x̂``, and ``message_bits(d)`` /
``bits_per_coord(d)`` (the WIRE accounting every algorithm's ``bits_up`` /
``bits_down`` metrics are computed from). ``hint`` is the encoder-local
distance estimate (position-aware codecs derive their scale from it;
others ignore it); ``ref`` is the decoder-side reference. Codecs carrying
cross-round encoder state (error feedback) set ``stateful = True`` and
implement ``init_state(d)`` + ``encode_stateful(key, x, hint, state) ->
(msg, state)``; algorithms that thread the state get error feedback,
everything else falls back to the stateless ``encode``.

**Registry** (mirroring the ``FedAlgorithm`` registry):

  ``lattice``         position-aware lattice quantizer (the paper's
                      default; unchanged math, word-aligned uint codes on
                      the wire — so 4-bit codes still ship 8 bits/coord)
  ``lattice_packed``  same math, sub-byte packed wire: ``8 // bits`` codes
                      per byte, packed inside the fused encode kernel and
                      unpacked in snap/decode (bits ∈ {1, 2, 4, 8})
  ``topk_ef``         position-aware top-k sparsification + error
                      feedback: transmit the k largest-|·| coordinates
                      (plus the carried residual when the algorithm threads
                      state); untransmitted coordinates decode to the
                      reference
  ``scalar``          FedPAQ/QSGD-style norm-scaled stochastic rounding
                      (NOT position-aware: error ∝ ‖x‖ — the §2.2 baseline)
  ``identity``        fp32 pass-through (32 bits/coord, no γ overhead)

Specs are strings — ``"lattice"``, ``"scalar:bits=4"``,
``"topk_ef:frac=0.05"`` — codec instances, or (uplink only) a
``{"fast": spec, "slow": spec}`` group map resolved against the client
speed classes into a :class:`GroupedLatticeCodec` with per-client bit
budgets (fast clients at b=8, stragglers at b=4 is one config knob).
Third-party codecs join via :func:`register_codec` and immediately work
with every registry algorithm, ``simulate()``, and the launch drivers
(``--codec-up`` / ``--codec-down``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.provenance import wire_mark
from repro.compression.pipeline import (GAMMA_NORM_FLOOR, LatticeWire,
                                        coord_bound, get_backend,
                                        wire_container_dtype, wrap_gamma)
from repro.compression.rotation import DEFAULT_BLOCK, _signs, dither, pad_len
from repro.kernels.exchange import pack_codes, unpack_codes


@runtime_checkable
class Codec(Protocol):
    """Structural type of a registered compression codec."""

    def encode(self, key, x, hint) -> Any:
        ...

    def decode(self, key, msg, ref) -> Any:
        ...

    def message_bits(self, d: int) -> int:
        ...


class LatticeMsg(NamedTuple):
    """One coded message: the payload and its transmitted scale."""
    codes: jnp.ndarray     # (d_pad,) codes (lattice: uints in [0, 2^b))
    gamma: jnp.ndarray     # () fp32 — transmitted scale (O(1) overhead)


# ---------------------------------------------------------------------------
# machine-readable wire declarations
# ---------------------------------------------------------------------------

class WirePart(NamedTuple):
    """One named component of a codec's per-message wire format.

    ``elems``/``container_bits`` describe what a trace must show at the
    matching ``wire_mark`` site (the physical value crossing the wire);
    ``charged_bits`` is this part's contribution to ``message_bits(d)``.
    The two may legitimately differ per coordinate (``scalar`` charges its
    entropy-coded b bits while shipping a whole int container), but a
    payload charged sub-16-bit that traces as a >= 32-bit container is a
    wire lie the audit rejects.
    """
    part: str             # "codes" | "idx" | "vals" | "gamma" | "levels"
    elems: int            # per-message element count on the wire
    container_bits: int   # traced dtype width at the wire_mark site
    charged_bits: int     # contribution to message_bits(d)
    kind: str             # "int" | "float"
    payload: bool         # coordinate payload vs. 32-bit side-channel row


class WireDecl(NamedTuple):
    """A codec's declared wire format, consumed by ``analysis/wire.py``.

    Replaces the prose convention ("lattice ships packed codes plus a γ
    scalar...") with data the gate can cross-check against traces:
    ``moduli`` are the wrap moduli the γ-overflow interval analysis must
    prove safe (empty for non-lattice codecs), ``safety`` the declared
    head-room factor of the wrap window.
    """
    codec: str
    parts: Tuple[WirePart, ...]
    moduli: Tuple[int, ...] = ()
    safety: float = 0.0

    @property
    def message_bits(self) -> int:
        return sum(p.charged_bits for p in self.parts)

    def part(self, name: str) -> WirePart | None:
        for p in self.parts:
            if p.part == name:
                return p
        return None

    @property
    def side_rows(self) -> Tuple[str, ...]:
        return tuple(p.part for p in self.parts if not p.payload)


class CodecBase:
    """Shared defaults: stateless, derived per-coordinate accounting."""
    stateful: bool = False
    # error-feedback residuals are the un-decoded remainder of the message,
    # which the encoder can only compute when it knows what the decoder
    # reconstructs — i.e. for DELTA-style messages decoded against the zero
    # vector. Algorithms whose uplink decodes against a non-zero reference
    # (QuAFL's model-vs-server exchange) must use the stateless encode.
    ef_zero_ref_only: bool = True

    def init_state(self, d: int):
        return ()

    def encode_stateful(self, key, x, hint, state):
        """Stateless fallback: EF-capable algorithms thread ``state``;
        everything else calls plain ``encode`` and the codec degrades
        gracefully (no residual memory)."""
        return self.encode(key, x, hint), state

    def bits_per_coord(self, d: int) -> float:
        return self.message_bits(d) / d


def init_client_states(codec, n: int, d: int):
    """Stacked per-client encoder state of a stateful codec (``()`` for
    stateless ones) — the shared helper behind every algorithm that
    threads error-feedback residuals."""
    if not codec.stateful:
        return ()
    st0 = codec.init_state(d)
    return jax.tree_util.tree_map(
        lambda a: jnp.tile(a[None], (n,) + (1,) * a.ndim), st0)


# ---------------------------------------------------------------------------
# identity / scalar
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class IdentityCodec(CodecBase):
    """fp32 pass-through; the 'uncompressed' point of the design space."""
    name: str = "identity"
    bits: int = 32

    def encode(self, key, x, hint=None):
        return LatticeMsg(
            codes=wire_mark(x, channel="msg", part="codes",
                            codec=self.name, d=int(x.shape[-1])),
            gamma=jnp.float32(1.0))

    def decode(self, key, msg, ref=None):
        return msg.codes

    def message_bits(self, d: int) -> int:
        return d * 32

    def wire_declaration(self, d: int) -> WireDecl:
        return WireDecl(codec=self.name, parts=(
            WirePart("codes", d, 32, d * 32, "float", True),))


@dataclass(frozen=True)
class ScalarCodec(CodecBase):
    """FedPAQ-style norm-scaled stochastic rounding (arXiv:2106.07155's
    quantizer; QSGD [Alistarh et al.]; the paper's Figure-5 'direct
    quantization' baseline). Not position-aware — ``ref`` is ignored and
    the error scales with ‖x‖."""
    bits: int = 8
    name: str = "scalar"

    @property
    def levels(self) -> int:
        return (1 << (self.bits - 1)) - 1  # signed levels

    def _container(self):
        # signed storage of levels in [-(2^(b-1)-1), 2^(b-1)-1]
        return jnp.int8 if self.bits <= 8 else (
            jnp.int16 if self.bits <= 16 else jnp.int32)

    def encode(self, key, x, hint=None):
        norm = jnp.linalg.norm(x) + 1e-12
        y = jnp.abs(x) / norm * self.levels
        u = dither(key, x.shape)
        q = jnp.floor(y + u) * jnp.sign(x)
        codes = wire_mark(q.astype(self._container()), channel="msg",
                          part="codes", codec=self.name, d=int(x.shape[-1]))
        gamma = wire_mark(norm, channel="msg", part="gamma",
                          codec=self.name, d=int(x.shape[-1]))
        return LatticeMsg(codes=codes, gamma=gamma)

    def decode(self, key, msg, ref=None):
        return msg.codes.astype(jnp.float32) * (msg.gamma / self.levels)

    def message_bits(self, d: int) -> int:
        return d * self.bits + 32

    def wire_declaration(self, d: int) -> WireDecl:
        container = jnp.dtype(self._container()).itemsize * 8
        return WireDecl(codec=self.name, parts=(
            WirePart("codes", d, container, d * self.bits, "int", True),
            WirePart("gamma", 1, 32, 32, "float", False)))


# ---------------------------------------------------------------------------
# lattice family
# ---------------------------------------------------------------------------

def _storage_bits(bits: int) -> int:
    """Wire width of one unpacked lattice code (see
    ``pipeline.wire_container_dtype``)."""
    return jnp.dtype(wire_container_dtype(LatticeWire(bits))).itemsize * 8


def _lattice_encode(c, key, x, dist_hint) -> LatticeMsg:
    """Enc(x) of one flat (d,) fp32 message at the lattice parameters of
    codec ``c`` (``bits``, ``block``, ``safety``, ``backend``). ``key`` is
    the interaction's shared rotation+rounding key (both ends derive it);
    ``dist_hint`` an upper estimate of ‖x − ref‖₂, from which γ is set so
    the wrap window 2^b·γ exceeds twice the max rotated difference
    coordinate (subgaussian with scale dist/sqrt(d))."""
    d = x.shape[0]
    d_pad = pad_len(d, c.block)
    gamma = wrap_gamma(jnp.asarray(dist_hint, jnp.float32), d, bits=c.bits,
                       block=c.block, safety=c.safety)
    # fp32 precision floor: the modulo decode needs y/γ (and w/γ) to
    # keep sub-integer precision, so γ ≥ max|rot(x)|·2^-18. The max
    # rotated coordinate is estimated pre-rotation from the (rotation-
    # invariant) norm so γ is available before the fused rotate+quantize
    # kernel runs. When the distance hint is tiny relative to the model
    # norm the error bound degrades to the model's own fp32 resolution
    # instead of silently mis-decoding.
    gamma = jnp.maximum(gamma, coord_bound(jnp.linalg.norm(x), d_pad)
                        * GAMMA_NORM_FLOOR)
    krot, krnd = jax.random.split(key)
    signs = _signs(krot, d_pad)
    u = dither(krnd, (d_pad,))
    x2 = jnp.pad(x.astype(jnp.float32), (0, d_pad - d))[None]
    codes = get_backend(c.backend).encode(x2, signs, u[None], gamma[None],
                                          bits=c.bits, block=c.block,
                                          want_rotated=False)[0]
    return LatticeMsg(
        codes=codes.astype(wire_container_dtype(LatticeWire(c.bits))),
        gamma=gamma)


def _lattice_decode(c, key, msg: LatticeMsg, ref) -> jnp.ndarray:
    """Dec(ref, msg): ref is the flat (d,) decoding key (the paper's y).
    One fused pass: rotate the reference, snap each code to the
    representative nearest the reference coordinate, inverse-rotate."""
    d = ref.shape[0]
    d_pad = pad_len(d, c.block)
    krot, _ = jax.random.split(key)
    signs = _signs(krot, d_pad)
    ref2 = jnp.pad(ref.astype(jnp.float32), (0, d_pad - d))[None]
    x = get_backend(c.backend).decode(msg.codes[None], ref2, signs,
                                      jnp.reshape(msg.gamma, (1,)),
                                      bits=c.bits, block=c.block)[0]
    return x[:d]


@dataclass(frozen=True)
class LatticeCodec(CodecBase):
    """Position-aware lattice quantizer (Davies et al. [7], Lemma 3.1).

    ``Enc(x)`` maps x to b-bit codes; ``Dec(y, Enc(x))`` recovers Q(x) with
    any reference y with ``‖x − y‖`` small: a randomized Hadamard rotation,
    then stochastically-rounded rotated coordinates mod 2^b, snapped by the
    decoder to the representative nearest its own rotated reference. While
    the wrap condition holds: E[Q(x)] = x, ‖Q(x) − x‖ ≤ γ·sqrt(d_pad), and
    d·b + O(1) bits. γ comes from the encoder-local distance hint, so the
    error is proportional to the model *distance*, never its norm (paper
    §2.2). The math runs on the pipeline backend ``backend``.

    ``packed=False`` ships word-aligned uint codes (8/16/32 bits per
    coordinate — the historical wire format, and the honest accounting of
    it); ``packed=True`` is the ``lattice_packed`` registry entry: sub-byte
    packing inside the fused encode kernel, exactly ``bits`` bits per
    coordinate on the wire (requires ``bits`` ∈ {1, 2, 4, 8}). The math is
    identical either way (pack ∘ unpack is the identity), so at b=8 the two
    codecs coincide and both reproduce the PR 3 exchange bit for bit.
    """
    bits: int = 8
    block: int = DEFAULT_BLOCK
    safety: float = 8.0
    backend: str = "jnp"
    packed: bool = False
    name: str = "lattice"
    family: str = "lattice"

    def __post_init__(self):
        if self.packed and self.bits not in (1, 2, 4, 8):
            raise ValueError(
                f"lattice_packed needs bits in {{1, 2, 4, 8}} (whole codes "
                f"per byte); got bits={self.bits}")

    @property
    def pack(self) -> int:
        return (8 // self.bits) if self.packed else 1

    def wire(self, idx=None) -> LatticeWire:
        """The fused-pipeline wire descriptor of this codec (``idx``, the
        sampled-client index set, only matters for grouped codecs)."""
        return LatticeWire(bits=self.bits, pack=self.pack)

    # -- per-message API (generic paths, mesh leaves, FedBuff deltas) ------
    def encode(self, key, x, hint):
        msg = _lattice_encode(self, key, x, hint)
        if self.pack > 1:
            codes = pack_codes(msg.codes[None].astype(jnp.uint32),
                               bits=self.bits, block=self.block)[0]
            msg = LatticeMsg(codes=codes, gamma=msg.gamma)
        return LatticeMsg(
            codes=wire_mark(msg.codes, channel="msg", part="codes",
                            codec=self.name, d=int(x.shape[-1])),
            gamma=wire_mark(msg.gamma, channel="msg", part="gamma",
                            codec=self.name, d=int(x.shape[-1])))

    def decode(self, key, msg, ref):
        if self.pack > 1:
            codes = unpack_codes(msg.codes[None], bits=self.bits,
                                 block=self.block)[0]
            unpacked = wire_container_dtype(LatticeWire(self.bits))
            msg = LatticeMsg(codes=codes.astype(unpacked), gamma=msg.gamma)
        return _lattice_decode(self, key, msg, ref)

    def message_bits(self, d: int) -> int:
        per = self.bits if self.packed else _storage_bits(self.bits)
        return pad_len(d, self.block) * per + 32  # + γ scalar

    def code_dtype(self):
        return wire_container_dtype(self.wire())

    def wire_declaration(self, d: int) -> WireDecl:
        dp = pad_len(d, self.block)
        per = self.bits if self.packed else _storage_bits(self.bits)
        # packed wire: d_pad/pack uint8 containers each holding `pack`
        # codes; unpacked: d_pad containers at the storage width
        container = jnp.dtype(self.code_dtype()).itemsize * 8
        return WireDecl(codec=self.name, parts=(
            WirePart("codes", dp // self.pack, container, dp * per,
                     "int", True),
            WirePart("gamma", 1, 32, 32, "float", False)),
            moduli=(1 << self.bits,), safety=self.safety)


@dataclass(frozen=True)
class GroupedLatticeCodec(CodecBase):
    """Heterogeneous per-client bit budgets over the lattice exchange.

    ``bits_per_client`` assigns each client its own bit-width; the fused
    rotated-space pipeline runs ONE batched exchange with per-message wrap
    moduli (``LatticeWire.levels``), so a round can mix b=8 fast clients
    with b=4 stragglers at no extra rotation passes. Runs on every kernel
    backend — the Pallas kernels take the moduli as a per-message SMEM
    operand next to the γ scalars. Uplink only (the downlink broadcast is one
    message).

    Wire accounting is the MEMBER codec's: ``wire_width_per_client[i]`` is
    the bits/coordinate the client's group declared — ``lattice`` members
    charge their word-aligned uint storage, ``lattice_packed`` members
    exactly their sub-byte width (each client's message is uniform-width,
    so per-message packing is well defined even though the batched
    pipeline computes on unpacked working arrays).
    """
    bits_per_client: Tuple[int, ...]
    wire_width_per_client: Tuple[int, ...]   # bits/coord on the wire
    block: int = DEFAULT_BLOCK
    safety: float = 8.0
    backend: str = "jnp"
    name: str = "lattice_grouped"
    family: str = "lattice"
    packed: bool = False

    def __post_init__(self):
        assert len(self.wire_width_per_client) == len(self.bits_per_client)
        object.__setattr__(self, "bits", int(max(self.bits_per_client)))
        object.__setattr__(self, "_levels_j", jnp.asarray(
            [1 << int(b) for b in self.bits_per_client], jnp.float32))

    @property
    def pack(self) -> int:
        return 1

    def wire(self, idx=None) -> LatticeWire:
        """Wire descriptor for the sampled client subset ``idx``."""
        levels = self._levels_j if idx is None else self._levels_j[idx]
        return LatticeWire(bits=self.bits, pack=1, levels=levels)

    def message_bits(self, d: int) -> int:
        # + γ scalar + the per-message wrap modulus (levels row): the
        # receiver cannot snap a heterogeneous-width message without its
        # modulus, so the row is charged wire traffic, not an exempt
        # side channel (it is audited via wire_declaration like any part)
        return (pad_len(d, self.block) * max(self.wire_width_per_client)
                + 64)

    def message_bits_per_client(self, d: int) -> np.ndarray:
        dp = pad_len(d, self.block)
        return np.asarray([dp * int(w) + 64
                           for w in self.wire_width_per_client], np.float32)

    def bits_for(self, idx, d: int):
        """Traced total uplink bits of the sampled subset ``idx``."""
        mb = jnp.asarray(self.message_bits_per_client(d))
        return jnp.sum(mb[idx])

    def wire_declaration(self, d: int) -> WireDecl:
        dp = pad_len(d, self.block)
        w_max = max(self.wire_width_per_client)
        return WireDecl(codec=self.name, parts=(
            WirePart("codes", dp, _storage_bits(self.bits), dp * w_max,
                     "int", True),
            WirePart("gamma", 1, 32, 32, "float", False),
            WirePart("levels", 1, 32, 32, "float", False)),
            moduli=tuple(sorted({1 << int(b)
                                 for b in self.bits_per_client})),
            safety=self.safety)

    # per-message API: encode/decode one client's message at ITS bit-width
    # is not expressible with a shared jit cache — the grouped codec exists
    # for the batched pipeline path. Fall back to max-bits messages.
    def encode(self, key, x, hint):
        return _lattice_encode(self, key, x, hint)

    def decode(self, key, msg, ref):
        return _lattice_decode(self, key, msg, ref)


# ---------------------------------------------------------------------------
# top-k sparsification + error feedback
# ---------------------------------------------------------------------------

class TopKMsg(NamedTuple):
    idx: jnp.ndarray    # (k,) int32 coordinate indices
    vals: jnp.ndarray   # (k,) f32 transmitted values


@dataclass(frozen=True)
class TopKEFCodec(CodecBase):
    """Position-aware top-k: ship the k largest-magnitude coordinates;
    every untransmitted coordinate decodes to the REFERENCE value, so the
    per-message error is bounded by ‖x − ref‖ restricted to the dropped
    support (and by ‖x‖ against a zero reference — the classic sparse-delta
    case). With threaded state (EF14/EF21 style, cf.
    ``repro.compression.error_feedback``) the untransmitted residual is
    remembered encoder-side and re-injected next round, so every coordinate
    is eventually transmitted. The residual equals ``target`` off the
    transmitted support — the encoding error ONLY when the decoder
    reconstructs zero there (``ef_zero_ref_only``): delta-style uplinks
    (FedBuff, compressed FedAvg) thread it; model-vs-server exchanges fall
    back to the stateless encode."""
    frac: float = 0.01      # fraction of coordinates transmitted
    k_min: int = 1
    name: str = "topk_ef"
    stateful: bool = True
    ef_zero_ref_only: bool = True

    def k_for(self, d: int) -> int:
        return max(self.k_min, int(round(self.frac * d)))

    def init_state(self, d: int):
        return jnp.zeros((d,), jnp.float32)

    def _encode(self, target):
        k = self.k_for(target.shape[0])
        _, idx = jax.lax.top_k(jnp.abs(target), k)
        idx = idx.astype(jnp.int32)
        d = int(target.shape[0])
        return TopKMsg(
            idx=wire_mark(idx, channel="msg", part="idx", codec=self.name,
                          d=d),
            vals=wire_mark(target[idx], channel="msg", part="vals",
                           codec=self.name, d=d))

    def encode(self, key, x, hint=None):
        return self._encode(x.astype(jnp.float32))

    def encode_stateful(self, key, x, hint, state):
        target = x.astype(jnp.float32) + state
        msg = self._encode(target)
        return msg, target.at[msg.idx].set(0.0)

    def decode(self, key, msg, ref):
        return ref.astype(jnp.float32).at[msg.idx].set(msg.vals)

    def message_bits(self, d: int) -> int:
        return self.k_for(d) * (32 + 32)  # (index, value) pairs

    def wire_declaration(self, d: int) -> WireDecl:
        k = self.k_for(d)
        return WireDecl(codec=self.name, parts=(
            WirePart("idx", k, 32, k * 32, "int", True),
            WirePart("vals", k, 32, k * 32, "float", True)))


# ---------------------------------------------------------------------------
# registry + spec resolution
# ---------------------------------------------------------------------------

def _build_lattice(*, bits, backend, block, safety, packed=False, **kw):
    _reject_extra(kw, "lattice")
    return LatticeCodec(bits=bits, block=block, safety=safety,
                        backend=backend, packed=packed,
                        name="lattice_packed" if packed else "lattice")


def _build_lattice_packed(**kw):
    return _build_lattice(packed=True, **kw)


def _build_scalar(*, bits, backend, block, safety, **kw):
    _reject_extra(kw, "scalar")
    return ScalarCodec(bits=bits)


def _build_identity(*, bits, backend, block, safety, **kw):
    _reject_extra(kw, "identity")
    return IdentityCodec()


def _build_topk_ef(*, bits, backend, block, safety, frac=0.01, **kw):
    _reject_extra(kw, "topk_ef")
    return TopKEFCodec(frac=float(frac))


def _reject_extra(kw: Dict[str, Any], name: str):
    if kw:
        raise ValueError(f"unknown codec parameter(s) {sorted(kw)} for "
                         f"{name!r}")


_CODECS: Dict[str, Any] = {
    "lattice": _build_lattice,
    "lattice_packed": _build_lattice_packed,
    "topk_ef": _build_topk_ef,
    "scalar": _build_scalar,
    "identity": _build_identity,
}


def registered_codecs() -> Tuple[str, ...]:
    """Names accepted by :func:`make_codec`, in registration order."""
    return tuple(_CODECS)


def register_codec(name: str, builder) -> None:
    """Register a custom codec. ``builder`` receives keyword arguments
    ``bits``, ``backend``, ``block``, ``safety`` plus any ``name:key=val``
    spec parameters, and must return a :class:`Codec`."""
    if name in _CODECS:
        raise ValueError(f"codec {name!r} already registered")
    _CODECS[name] = builder


def _parse_spec(spec: str) -> Tuple[str, Dict[str, Any]]:
    """'name' or 'name:k=v,k=v' -> (name, {k: parsed_v})."""
    name, _, tail = spec.partition(":")
    params: Dict[str, Any] = {}
    if tail:
        for item in tail.split(","):
            k, _, v = item.partition("=")
            if not _ or not k:
                raise ValueError(f"malformed codec spec {spec!r} "
                                 f"(want name:key=val,key=val)")
            try:
                params[k.strip()] = int(v)
            except ValueError:
                params[k.strip()] = float(v)
    return name.strip(), params


def make_codec(spec, *, bits: int = 8, backend: str = "jnp",
               block: int = DEFAULT_BLOCK, safety: float = 8.0) -> Codec:
    """Build a codec from a spec string (or pass a codec instance through).

    ``bits`` / ``backend`` / ``block`` / ``safety`` are the config-derived
    defaults; a ``bits=`` in the spec string overrides the config value.
    """
    if not isinstance(spec, str):
        if isinstance(spec, Codec):
            return spec
        raise TypeError(f"codec spec must be a name string or codec "
                        f"instance (group dicts resolve through "
                        f"resolve_codec); got {type(spec).__name__}")
    name, params = _parse_spec(spec)
    if name not in _CODECS:
        raise ValueError(f"unknown codec {name!r}; choose from "
                         f"{sorted(_CODECS)}")
    bits = int(params.pop("bits", bits))
    safety = float(params.pop("safety", safety))
    block = int(params.pop("block", block))
    return _CODECS[name](bits=bits, backend=backend, block=block,
                         safety=safety, **params)


def resolve_codec(spec, fed, *, direction: str, default: str,
                  slow_mask=None) -> Codec:
    """Resolve an algorithm's per-direction codec.

    Precedence: explicit ``spec`` kwarg > ``fed.codec_up`` /
    ``fed.codec_down`` > the algorithm's ``default``. A dict spec
    ``{"fast": ..., "slow": ...}`` (uplink only) resolves each group and
    combines lattice-family members into a :class:`GroupedLatticeCodec`
    over ``slow_mask`` (the boolean per-client straggler mask from the
    clock's speed model).
    """
    backend = getattr(fed, "kernel_backend", "jnp")
    if spec is None:
        spec = getattr(fed, f"codec_{direction}", "") or default
    if isinstance(spec, dict):
        if direction != "up":
            raise ValueError("per-client group codecs apply to the uplink "
                             "only (the downlink is one broadcast message)")
        if slow_mask is None:
            raise ValueError("group codec specs need the algorithm's "
                             "client speed classes (slow_mask)")
        members = {g: make_codec(s, bits=fed.bits, backend=backend)
                   for g, s in spec.items()}
        unknown = set(members) - {"fast", "slow"}
        if unknown:
            raise ValueError(f"unknown client groups {sorted(unknown)}; "
                             f"use 'fast' / 'slow'")
        fast = members.get("fast")
        slow = members.get("slow", fast)
        fast = fast if fast is not None else slow
        if not all(isinstance(c, LatticeCodec) for c in (fast, slow)):
            raise NotImplementedError(
                "per-client group codecs currently compose lattice-family "
                "members only")
        if (fast.safety, fast.block) != (slow.safety, slow.block):
            raise ValueError("group members must share safety/block (one "
                             "batched exchange, one γ derivation)")

        def width(c: LatticeCodec) -> int:
            # the member's own declared wire: packed members charge their
            # sub-byte width, unpacked ones their uint storage
            return c.bits if c.packed else _storage_bits(c.bits)

        mask = np.asarray(slow_mask)
        bits = tuple(int(slow.bits) if bool(m) else int(fast.bits)
                     for m in mask)
        widths = tuple(width(slow) if bool(m) else width(fast)
                       for m in mask)
        return GroupedLatticeCodec(bits_per_client=bits,
                                   wire_width_per_client=widths,
                                   block=fast.block, safety=fast.safety,
                                   backend=backend)
    return make_codec(spec, bits=fed.bits, backend=backend)


def is_lattice_family(codec) -> bool:
    """True when the fused rotated-space pipeline can carry this codec."""
    return getattr(codec, "family", "") == "lattice"
