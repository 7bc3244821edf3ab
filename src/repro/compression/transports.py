"""Transport protocol: HOW the uplink aggregate moves over the mesh.

A codec decides what one message looks like; a transport decides how the
client-sum collective of the shard-local exchange
(:mod:`repro.core.exchange_local`) is carried over the interconnect. All
three strategies compute the SAME aggregate (they are pinned against each
other in ``tests/test_distributed.py``); they differ only in which bytes
cross the wire:

  ``shard_local``     decode/snap locally, all-reduce fp32 partial sums —
                      the faithful reading of Alg. 1 line 8 on a pod
                      (legacy name ``dequant_psum``)
  ``code_allgather``  all-gather the PACKED codec codes (uint8/16 — or the
                      sub-byte ``lattice_packed`` bytes, at b=4 HALF the
                      unpacked payload) + decode every message locally
  ``reduce_scatter``  snap locally in rotated space, ``psum_scatter`` the
                      snapped chunks over the client axis, then move the
                      reduced shards back as a SCATTER-RESIDENT COMPRESSED
                      downlink: each device lattice-encodes its own reduced
                      shard and the all-gather carries packed integer codes
                      plus a γ-shards row instead of fp32 — the receiver
                      snaps the gathered codes against n·rot(X_t) post-
                      gather. The redistribution phase moves width/32 of
                      the fp32 re-gather bytes (b=4 packed: 1/8). The
                      aggregate is re-quantized at the downlink wire width
                      (the per-client lattices share no common grid, so an
                      exact coded re-gather is impossible); the error obeys
                      the same Lemma 3.1 wrap bound as the downlink encode
                      and the transport stays bit-identical across kernel
                      backends.

``shard_local`` and ``code_allgather`` compute the SAME aggregate (pinned
against each other in ``tests/test_distributed.py``); ``reduce_scatter``
agrees up to its γ_rs·√d̄ redistribution quantization, also pinned there.
Each transport exposes ``lattice_sum`` (rotated-space fused path) and
``generic_sum`` (per-message codec path); ``reduce_scatter`` additionally
exposes ``lattice_fused_sum`` (the scatter-resident coded path — the
shard-local exchange prefers it when present) and every transport reports
its gathered fp32 side-channel rows via ``extra_bits_down`` so the wire
accounting in :mod:`repro.launch.spmd` stays honest. The registry mirrors
the codec/algorithm registries: select by name
(``FedConfig.transport = "shard_local_rs"`` maps here via
:func:`transport_for_mode`), extend via :func:`register_transport`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, NamedTuple, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp

from repro.analysis.provenance import wire_mark
from repro.kernels.exchange import block_geometry
from repro.compression.pipeline import wire_container_dtype
from repro.compression.rotation import dither, pad_len


class WireBudget(NamedTuple):
    """A transport's declared collective footprint for ONE exchanged leaf.

    ``caps`` upper-bounds every collective class the wire-truth audit
    meters (:func:`repro.analysis.jaxpr.collective_bytes` keys, bytes); a
    zero cap asserts the collective class is absent. ``float_reduce_ok``
    states whether model-sized fp32 payloads may enter reduce-class
    collectives (psum / psum_scatter) — the design of ``shard_local`` and
    ``reduce_scatter``, a wire leak on ``code_allgather``. These replace
    the hand-pinned byte caps the PR 9 ``rs_transport_audit`` carried.
    """
    caps: Dict[str, int]
    float_reduce_ok: bool


# scalar side traffic per exchanged leaf (hint/qerr psums): a loose upper
# bound, far below any model payload
_SCALAR_SLACK = 256


def _leaf_dpad(codec, d: int) -> int:
    """Padded length of one exchanged leaf: the shard-local exchange pads
    leaves to 1024 then the pipeline pads to its block geometry."""
    d1 = d + (-d) % 1024
    blk = getattr(codec, "block", None)
    return pad_len(d1) if blk is None else pad_len(d1, blk)


def _lattice_pair(codec_up, codec_down) -> bool:
    return (getattr(codec_up, "family", "") == "lattice"
            and getattr(codec_down, "family", "") == "lattice")


def _decl_gather_bytes(decl, n: int) -> Tuple[int, int]:
    """(int_bytes, float_bytes) an all-gather of one declared message
    costs per device (output = n stacked messages)."""
    ib = fb = 0
    for p in decl.parts:
        nbytes = n * p.elems * (p.container_bits // 8)
        if p.kind == "int":
            ib += nbytes
        else:
            fb += nbytes
    return ib, fb


@runtime_checkable
class Transport(Protocol):
    """Structural type of a registered uplink-aggregation strategy."""

    def lattice_sum(self, pipe, wire, codes, gammas, srv_rot, qy_own,
                    client_axis, in_mesh, code_dtype):
        ...

    def generic_sum(self, quant, key, msg, srv, qy_own, client_axis,
                    in_mesh, n_slots):
        ...


def _gather_flat(x, axis):
    """``all_gather`` of a flat wire vector: (len,) -> (n, len).

    The vector travels as (len / 128, 128) rows where it tiles. The TPU
    compiler takes minutes over an all-gather of one long 1-D array (the
    262M uint8 codes of llama3.2-1b's embedding: ~8 min on a v5e compile)
    and seconds over the same bytes as rows."""
    if x.shape[-1] % 128:
        return jax.lax.all_gather(x, axis)
    rows = jax.lax.all_gather(x.reshape(-1, 128), axis)
    return rows.reshape(rows.shape[0], -1)


def _psum_maybe(x, axis, in_mesh):
    return jax.lax.psum(x, axis) if in_mesh else x


def _shardable(d_pad: int, n: int, wire, block=None) -> bool:
    """Can a (1, d_pad) rotated vector be coded per reduce-scatter shard?
    Each shard must be its own valid block geometry (no repadding inside
    the collective) and, when the wire packs sub-byte, the shard's Hadamard
    sublane factor must still divide by ``pack``."""
    if n <= 1 or d_pad % n:
        return False
    d_sh = d_pad // n
    blk = {} if block is None else {"block": block}
    if pad_len(d_sh, **blk) != d_sh:
        return False
    _, _, r, _, _ = block_geometry(d_sh, **blk)
    return wire.pack == 1 or r % wire.pack == 0


def scatter_encode_gather(pipe, wire, vec_rot, ref_rot, gammas, key, n: int):
    """Single-host emulation of the scatter-resident coded redistribution.

    Splits the summed ROTATED vector (1, d_pad) into the ``n`` shards a
    ``psum_scatter`` leaves resident on each device, lattice-encodes every
    shard at the wire's width (what the all-gather would move), and snaps
    the gathered codes against the matching shards of ``ref_rot`` — the
    same kernel calls the distributed ``lattice_fused_sum`` makes, minus
    the collectives. Returns ``(decoded (1, d_pad), packed_codes
    (n, d_sh // pack))`` for benches and backend-equivalence tests.
    """
    d_pad = vec_rot.shape[-1]
    d_sh = d_pad // n
    shards = vec_rot.reshape(n, d_sh)
    gam_row = jnp.broadcast_to(jnp.asarray(gammas, jnp.float32).reshape(-1),
                               (n,))
    u = dither(key, shards.shape)
    codes = pipe.quantize(shards, u, gam_row, wire)
    dec = pipe.snap(codes, ref_rot.reshape(n, d_sh), gam_row, wire)
    return dec.reshape(1, d_pad), codes


@dataclass(frozen=True)
class ShardLocalPsum:
    """fp32 all-reduce of locally decoded/snapped messages."""
    name: str = "shard_local"

    def lattice_sum(self, pipe, wire, codes, gammas, srv_rot, qy_own,
                    client_axis, in_mesh, code_dtype):
        return _psum_maybe(qy_own, client_axis, in_mesh)

    def generic_sum(self, quant, key, msg, srv, qy_own, client_axis,
                    in_mesh, n_slots):
        return _psum_maybe(qy_own, client_axis, in_mesh)

    def extra_bits_down(self, codec_up, codec_down, d: int, n: int) -> int:
        """The psum reduction moves no extra redistribution payload."""
        return 0

    def wire_budget(self, codec_up, codec_down, d: int, n: int) -> WireBudget:
        """One fp32 all-reduce of the decoded partials; nothing gathered."""
        dp = _leaf_dpad(codec_up, d)
        return WireBudget(caps={
            "psum_fbytes": dp * 4 + _SCALAR_SLACK,
            "psum_ibytes": 0,
            "psum_scatter_fbytes": 0,
            "psum_scatter_ibytes": 0,
            "reduce_scatter_fbytes": 0,
            "reduce_scatter_ibytes": 0,
            "all_gather_fbytes": 0,
            "all_gather_ibytes": 0,
        }, float_reduce_ok=True)


@dataclass(frozen=True)
class CodeAllgather:
    """All-gather packed codes along the client axis; decode locally.

    Moves ``codec.message_bits`` per client over the interconnect instead
    of d fp32 words — with the ``lattice_packed`` codec the gathered bytes
    shrink by the packing factor too.
    """
    name: str = "code_allgather"

    def lattice_sum(self, pipe, wire, codes, gammas, srv_rot, qy_own,
                    client_axis, in_mesh, code_dtype):
        if not in_mesh:
            return qy_own
        # the gathered operands ARE the wire: marked in their container
        # form so the wire-truth audit can cross-check the collective
        d_leaf = int(codes.shape[-1]) * max(int(wire.pack), 1)
        codes_all = _gather_flat(
            wire_mark(codes[0].astype(code_dtype), channel="up",
                      part="codes", codec="wire", d=d_leaf), client_axis)
        gam_all = jax.lax.all_gather(
            wire_mark(gammas[0], channel="up", part="gamma", codec="wire",
                      d=d_leaf), client_axis)
        return jnp.sum(pipe.snap(codes_all, srv_rot, gam_all, wire), 0,
                       keepdims=True)

    def generic_sum(self, quant, key, msg, srv, qy_own, client_axis,
                    in_mesh, n_slots):
        if not in_mesh:
            return qy_own
        # gather every message leaf (codes, scales, indices, ...) so ANY
        # codec's wire format rides this transport
        msg_all = jax.tree_util.tree_map(
            lambda a: jax.lax.all_gather(a, client_axis), msg)
        qy_sum = jnp.zeros_like(srv)
        for j in range(n_slots):
            m_j = jax.tree_util.tree_map(lambda a, j=j: a[j], msg_all)
            qy_sum = qy_sum + quant.decode(key, m_j, srv)
        return qy_sum

    def extra_bits_down(self, codec_up, codec_down, d: int, n: int) -> int:
        """The gathered per-client γ (and, for a grouped uplink, levels)
        f32 scalars are redistribution traffic: every device receives every
        other client's rows. ``message_bits`` already charges each client's
        OWN γ once (uplink); the other n-1 copies land here."""
        rows = 1
        wire = codec_up.wire() if hasattr(codec_up, "wire") else None
        if wire is not None and getattr(wire, "levels", None) is not None:
            rows += 1
        return rows * (n - 1) * 32

    def wire_budget(self, codec_up, codec_down, d: int, n: int) -> WireBudget:
        """Gathers exactly the declared uplink message (codes + side rows);
        reduce-class collectives carry scalars only."""
        decl = codec_up.wire_declaration(_leaf_dpad(codec_up, d))
        ib, fb = _decl_gather_bytes(decl, n)
        return WireBudget(caps={
            "psum_fbytes": _SCALAR_SLACK,
            "psum_ibytes": 0,
            "psum_scatter_fbytes": 0,
            "psum_scatter_ibytes": 0,
            "reduce_scatter_fbytes": 0,
            "reduce_scatter_ibytes": 0,
            "all_gather_fbytes": fb + _SCALAR_SLACK,
            "all_gather_ibytes": ib,
        }, float_reduce_ok=False)


@dataclass(frozen=True)
class ReduceScatterSum:
    """Reduce-scatter the snapped rotated chunks; coded shard re-gather.

    ``psum = reduce_scatter + all_gather``; carrying the sum as an explicit
    reduce-scatter halves the payload of the reducing phase AND leaves each
    device holding its reduced shard — so the redistribution is encoded
    scatter-resident: every device lattice-quantizes its OWN shard of the
    aggregate at the downlink wire width and the all-gather moves packed
    integer codes plus the (n,) γ-shards row instead of fp32. The receiver
    reassembles the gathered per-shard codes as an (n, d_sh) message batch
    and snaps them against the matching shards of the reference n·rot(X_t)
    — the Lemma 3.1 wrap bound holds with hint Σᵢ‖QYᵢ − rot(X_t)‖ by the
    triangle inequality. Falls back to the plain psum (exact, uncoded) when
    the chunk does not tile into valid per-shard block geometries
    (:func:`_shardable`) or outside the mesh.
    """
    name: str = "reduce_scatter"

    @staticmethod
    def _rs_ag(x, axis, n):
        d = x.shape[-1]
        if n <= 1 or d % n:
            return jax.lax.psum(x, axis)
        shard = jax.lax.psum_scatter(x, axis, scatter_dimension=x.ndim - 1,
                                     tiled=True)
        return jax.lax.all_gather(shard, axis, axis=x.ndim - 1, tiled=True)

    def lattice_sum(self, pipe, wire, codes, gammas, srv_rot, qy_own,
                    client_axis, in_mesh, code_dtype):
        if not in_mesh:
            return qy_own
        return self._rs_ag(qy_own, client_axis,
                           jax.lax.psum(1, client_axis))

    def lattice_fused_sum(self, pipe, wire, qy_own, srv_rot, gam_rs, key,
                          client_axis):
        """Scatter-resident compressed redistribution of the client sum.

        ``gam_rs`` is the (1,) redistribution scale (identical on every
        device — derived from psum'd hints); ``key`` seeds the per-device
        stochastic-rounding noise (decode never needs it). Returns the
        re-quantized (1, d_pad) rotated aggregate, bit-identical on every
        device (same gathered codes, same replicated reference).
        """
        n = jax.lax.psum(1, client_axis)
        d_pad = qy_own.shape[-1]
        if not _shardable(d_pad, n, wire, pipe.block):
            return jax.lax.psum(qy_own, client_axis)
        d_sh = d_pad // n
        shard = jax.lax.psum_scatter(qy_own, client_axis,
                                     scatter_dimension=qy_own.ndim - 1,
                                     tiled=True)            # (1, d_sh)
        u = dither(key, shard.shape)
        codes_sh = pipe.quantize(shard, u, gam_rs, wire)    # (1, d_sh//pack)
        # the wire: packed integer codes + the γ-shards row, NOT fp32. The
        # gather moves the codes in their declared storage container (the
        # working uint32 of the unpacked path would quadruple the bytes);
        # snap consumes any uint container, as on the code_allgather path.
        codes_all = _gather_flat(
            wire_mark(codes_sh[0].astype(wire_container_dtype(wire)),
                      channel="down", part="codes", codec="wire", d=d_sh),
            client_axis)
        gam_all = jax.lax.all_gather(
            wire_mark(gam_rs[0], channel="down", part="gamma",
                      codec="wire", d=d_sh), client_axis)   # (n,) f32
        ref_sh = (float(n) * srv_rot).reshape(n, d_sh)
        qy_hat = pipe.snap(codes_all, ref_sh, gam_all, wire)
        return qy_hat.reshape(1, d_pad)

    def generic_sum(self, quant, key, msg, srv, qy_own, client_axis,
                    in_mesh, n_slots):
        if not in_mesh:
            return qy_own
        return self._rs_ag(qy_own, client_axis, n_slots)

    def extra_bits_down(self, codec_up, codec_down, d: int, n: int) -> int:
        """The coded shard re-gather replaces the old (uncharged) fp32
        all-gather: every device receives one downlink-width code message
        plus the n-1 other γ shards — the codec's own wire math, moved into
        ``bits_down``."""
        if not hasattr(codec_down, "wire"):
            return 0   # generic codec pair: plain rs+ag of fp32 partials
        blk = getattr(codec_down, "block", None)
        d_pad = pad_len(d) if blk is None else pad_len(d, blk)
        if not _shardable(d_pad, n, codec_down.wire(), blk):
            return 0   # exact-psum fallback: reduction traffic only
        return codec_down.message_bits(d) + (n - 1) * 32

    def wire_budget(self, codec_up, codec_down, d: int, n: int) -> WireBudget:
        """Fused path: one psum_scatter of the fp32 partials + the coded
        shard re-gather at the downlink width. The tight psum cap asserts
        the fused path actually engaged (a silent fallback to plain psum
        is a byte-budget regression, not a numerics bug)."""
        dp = _leaf_dpad(codec_up, d)
        fused = (_lattice_pair(codec_up, codec_down)
                 and _shardable(dp, n, codec_down.wire(),
                                getattr(codec_down, "block", None)))
        if fused:
            decl = codec_down.wire_declaration(dp)
            codes = decl.part("codes")
            return WireBudget(caps={
                "psum_fbytes": _SCALAR_SLACK,
                "psum_ibytes": 0,
                # lax.psum_scatter lowers to the reduce_scatter
                # primitive; cap both names so neither leaks uncapped
                "psum_scatter_fbytes": dp * 4,
                "psum_scatter_ibytes": 0,
                "reduce_scatter_fbytes": dp * 4,
                "reduce_scatter_ibytes": 0,
                # gathered: every device ends with the full d_pad of codes
                # (n shards of d_sh) + the (n,) γ-shards row
                "all_gather_ibytes": codes.elems * (codes.container_bits
                                                    // 8),
                "all_gather_fbytes": n * 4 + _SCALAR_SLACK,
            }, float_reduce_ok=True)
        # generic pair / non-tiling geometry: rs+ag (or plain psum) of fp32
        return WireBudget(caps={
            "psum_fbytes": dp * 4 + _SCALAR_SLACK,
            "psum_ibytes": 0,
            "psum_scatter_fbytes": dp * 4,
            "psum_scatter_ibytes": 0,
            "reduce_scatter_fbytes": dp * 4,
            "reduce_scatter_ibytes": 0,
            "all_gather_fbytes": dp * 4 + _SCALAR_SLACK,
            "all_gather_ibytes": 0,
        }, float_reduce_ok=True)


_TRANSPORTS: Dict[str, object] = {
    "shard_local": ShardLocalPsum(),
    "code_allgather": CodeAllgather(),
    "reduce_scatter": ReduceScatterSum(),
}

# FedConfig.transport strings -> (runs the shard_map exchange?, registry
# name of the client-sum strategy). dequant_psum / code_allgather keep the
# legacy vmap composition in repro.launch.steps; the shard_local* family
# runs repro.core.exchange_local with the named strategy.
_MODE_MAP: Dict[str, str] = {
    "shard_local": "shard_local",
    "dequant_psum": "shard_local",
    "shard_local_codes": "code_allgather",
    "shard_local_rs": "reduce_scatter",
}


def registered_transports() -> Tuple[str, ...]:
    return tuple(_TRANSPORTS)


def register_transport(name: str, transport) -> None:
    if name in _TRANSPORTS:
        raise ValueError(f"transport {name!r} already registered")
    _TRANSPORTS[name] = transport


def make_transport(name: str):
    if name not in _TRANSPORTS:
        raise ValueError(f"unknown transport {name!r}; choose from "
                         f"{sorted(_TRANSPORTS)}")
    return _TRANSPORTS[name]


def transport_for_mode(fed_transport: str):
    """Map a ``FedConfig.transport`` string onto the shard-local exchange's
    client-sum strategy (``None`` = the transport is not a shard_map one)."""
    name = _MODE_MAP.get(fed_transport)
    return make_transport(name) if name is not None else None
