"""Pallas TPU kernels: fused rotated-space lattice exchange.

The per-round hot path of the quantized exchange is ``rotate -> stochastic
round -> wrap`` on the way out and ``snap -> inverse rotate`` on the way
back, over every sampled client's full model vector. The seed composition
materialized every intermediate (rotated coords, scaled coords, rounded
integers) in HBM; these kernels fuse each direction into one VMEM-resident
pass per (r, c) Hadamard block:

  * ``fused_rotate``  — sign flip + H_r @ X @ H_c / sqrt(rc) (fwd or inv)
  * ``fused_encode``  — rotate + floor(y/gamma + u) mod 2^b in one pass;
                        optionally also emits the rotated coords (the
                        rotated-space pipeline reuses them as the decode
                        reference, so the extra output replaces a whole
                        second rotation pass)
  * ``quantize_codes``— stochastic round + wrap of ALREADY-ROTATED coords:
                        the elementwise second half of ``fused_encode``. The
                        pipeline uses it to encode the server downlink from
                        its cached rotated coordinates, dropping the round's
                        forward-rotation budget from s+2 to s+1
  * ``snap_codes``    — positional snap only (stay in rotated space; the
                        pipeline averages rotated vectors and inverse-rotates
                        once at the end of the round)
  * ``fused_decode``  — rotate the reference + snap + inverse rotate: the
                        full ``Dec(ref, msg)`` in one pass (used by the
                        leaf-wise transport and the quantizer API)

**Sub-byte packing** (the ``lattice_packed`` codec): for ``bits`` in
{1, 2, 4} the encode-side kernels accept ``pack = 8 // bits`` and emit
``pack`` codes per byte — packed along the SUBLANE (r) axis of each
(r, c) Hadamard block, so the combine is a static reshape + shift-sum that
never crosses the 128-wide lane dimension — and the snap/decode kernels
unpack the same layout inline. The packed wire dtype is uint8 with
``d_pad // pack`` elements: at b=4 the codes tensor (what the
code_allgather transport moves over the interconnect) is exactly half the
unpacked uint8 bytes. ``pack=1`` (the default, and any ``bits >= 8``) is
bit-for-bit the historical unpacked path. :func:`pack_codes` /
:func:`unpack_codes` are the jnp reference implementations of the same
layout (used by the ``jnp`` backend and the per-message codec API).

All kernels run over a ``(m, nb)`` grid — ``m`` messages by ``nb`` Hadamard
blocks — with one (r, c) block per step; the two small Hadamard factors hit
the MXU directly. Batched operands broadcast along ``m`` through the block
index maps (no HBM materialization of the broadcast). Per-message scales
``gamma`` ride whole in SMEM as an (m, 1) column and each grid step reads
its message's scalar at ``program_id(0)``: a per-message VMEM row would
need a (1, 128) block over an (m, 128) array, which Mosaic refuses for
m > 1 (the last two block dims must be multiples of (8, 128) or the full
array dims).

**Per-message levels** (``GroupedLatticeCodec``): each quantizing kernel
optionally takes ``levels2`` — per-message wrap moduli (powers of two
<= ``2^bits``) riding as a second (m, 1) SMEM operand, the same layout
as the γ scalars. The kernel reads the modulus from it instead of the
static ``2^bits`` constant, so one batched call mixes
heterogeneous client bit budgets. Sub-byte packing stays at the STATIC
``bits`` container width: every per-message modulus is <= ``2^bits`` by
construction, so each code fits the container; honest per-member wire
bits are the codec's accounting job (`GroupedLatticeCodec.bits_for`),
not the storage layout's.

Codes are computed as float, stored as uint32: the float <-> uint32
casts go through int32 (Mosaic lowers no direct float <-> uint32 convert;
codes lie in [0, 2^bits), so the detour changes no value).

Every wrapper takes ``interpret`` without a default: the ``pallas``
backend compiles for the TPU, the ``pallas_interpret`` backend and the
tests run the same bodies through the Pallas interpreter.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.geometry import (DEFAULT_BLOCK, block_size, factor,
                                    hadamard_matrix, mm_f32, pad_len)

# a whole (m, 1) per-message scalar operand, resident in SMEM
_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _scalars(vals, m: int) -> jnp.ndarray:
    """Per-message scalars (γ or levels) as an (m, 1) f32 SMEM operand.

    The trailing unit dim keeps the operand legal under ``vmap``: the
    batched block is then (squeezed, m, 1), whose last two dims equal the
    array's, as Mosaic requires; a flat (m,) would batch to (sq, m).
    """
    return jnp.broadcast_to(jnp.asarray(vals, jnp.float32).reshape(-1, 1),
                            (m, 1))


def block_geometry(d: int, block: int = DEFAULT_BLOCK):
    """(b, d_pad, r, c, nb) for a length-d vector under ``block``-blocking."""
    b = block_size(d, block)
    d_pad = pad_len(d, block)
    r, c = factor(b)
    return b, d_pad, r, c, d_pad // b


def _had(r: int, c: int):
    return jnp.asarray(hadamard_matrix(r)), jnp.asarray(hadamard_matrix(c))


def _check_pack(pack: int, bits: int, r: int):
    if pack == 1:
        return
    if pack * bits != 8:
        raise ValueError(f"pack={pack} requires pack*bits == 8 "
                         f"(got bits={bits})")
    if r % pack:
        raise ValueError(f"pack={pack} does not divide the Hadamard "
                         f"sublane factor r={r}; vector too small to pack")


def _pack_block(q, pack: int, bits: int):
    """(r, c) integral float codes -> (r//pack, c) uint8, packed along
    sublanes. Shifts and sums run in int32: Mosaic reduces no unsigned
    integers, and a packed byte is < 256."""
    r, c = q.shape
    qi = q.astype(jnp.int32).reshape(r // pack, pack, c)
    shifts = (jnp.arange(pack, dtype=jnp.int32) * bits)[None, :, None]
    return jnp.sum(qi << shifts, axis=1).astype(jnp.uint8)


def _unpack_block(p, pack: int, bits: int):
    """(r//pack, c) packed uint8 -> (r, c) uint32 codes."""
    rp, c = p.shape
    pi = p.astype(jnp.uint32)[:, None, :]
    shifts = (jnp.arange(pack, dtype=jnp.uint32) * bits)[None, :, None]
    mask = jnp.uint32((1 << bits) - 1)
    return ((pi >> shifts) & mask).reshape(rp * pack, c)


def pack_codes(codes2: jnp.ndarray, *, bits: int,
               block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """(m, d_pad) codes -> (m, d_pad // (8//bits)) uint8, block-sublane
    packed — the ``lattice_packed`` wire layout (jnp reference)."""
    pack = 8 // bits
    m, d_pad = codes2.shape
    _, _, r, c, nb = block_geometry(d_pad, block)
    _check_pack(pack, bits, r)
    x = codes2.astype(jnp.uint32).reshape(m, nb, r // pack, pack, c)
    shifts = (jnp.arange(pack, dtype=jnp.uint32) * bits
              ).reshape(1, 1, 1, pack, 1)
    return jnp.sum(x << shifts, axis=3).astype(jnp.uint8).reshape(
        m, d_pad // pack)


def unpack_codes(packed2: jnp.ndarray, *, bits: int,
                 block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """Inverse of :func:`pack_codes`: (m, d_pad//pack) uint8 -> (m, d_pad)
    uint32."""
    pack = 8 // bits
    m, dp = packed2.shape
    d_pad = dp * pack
    _, _, r, c, nb = block_geometry(d_pad, block)
    _check_pack(pack, bits, r)
    x = packed2.astype(jnp.uint32).reshape(m, nb, r // pack, 1, c)
    shifts = (jnp.arange(pack, dtype=jnp.uint32) * bits
              ).reshape(1, 1, 1, pack, 1)
    mask = jnp.uint32((1 << bits) - 1)
    return ((x >> shifts) & mask).reshape(m, d_pad)


def _row_spec(m: int, r: int, c: int):
    """BlockSpec for a (m_or_1, nb, r, c) operand broadcast along the grid's
    message axis when its leading dim is 1."""
    if m == 1:
        return pl.BlockSpec((1, 1, r, c), lambda i, j: (0, j, 0, 0))
    return pl.BlockSpec((1, 1, r, c), lambda i, j: (i, j, 0, 0))


def _blk(x2: jnp.ndarray, nb: int, r: int, c: int):
    return x2.reshape(x2.shape[0], nb, r, c)


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _rotate_kernel(x_ref, s_ref, hr_ref, hc_ref, o_ref, *, scale: float,
                   inverse: bool):
    x = x_ref[0, 0].astype(jnp.float32)
    if not inverse:
        x = x * s_ref[0]
    y = mm_f32(hr_ref[...], x)
    y = mm_f32(y, hc_ref[...]) * scale
    if inverse:
        y = y * s_ref[0]
    o_ref[0, 0] = y


def _bits_of(levels: int) -> int:
    return int(levels).bit_length() - 1


def _modulus(l_ref, levels: int):
    """Wrap/snap modulus: this message's entry of the levels operand when
    one rides along (grouped codecs), else the static 2^bits container."""
    return float(levels) if l_ref is None else l_ref[pl.program_id(0), 0]


def _to_codes(q):
    """Integral float codes in [0, 2^bits) -> uint32, through int32."""
    return q.astype(jnp.int32).astype(jnp.uint32)


def _from_codes(c):
    """uint32 codes -> float32, through int32."""
    return c.astype(jnp.int32).astype(jnp.float32)


def _encode_kernel(x_ref, s_ref, u_ref, hr_ref, hc_ref, g_ref, l_ref, c_ref,
                   y_ref, *, scale: float, levels: int, want_rotated: bool,
                   pack: int = 1):
    x = x_ref[0, 0].astype(jnp.float32) * s_ref[0]
    y = mm_f32(hr_ref[...], x)
    y = mm_f32(y, hc_ref[...]) * scale
    g = g_ref[pl.program_id(0), 0]
    q = jnp.floor(y / g + u_ref[0, 0])
    q = jnp.mod(q, _modulus(l_ref, levels))
    c_ref[0, 0] = (_to_codes(q) if pack == 1
                   else _pack_block(q, pack, _bits_of(levels)))
    if want_rotated:
        y_ref[0, 0] = y


def _quantize_kernel(y_ref, u_ref, g_ref, l_ref, c_ref, *, levels: int,
                     pack: int = 1):
    g = g_ref[pl.program_id(0), 0]
    q = jnp.floor(y_ref[0, 0].astype(jnp.float32) / g + u_ref[0, 0])
    q = jnp.mod(q, _modulus(l_ref, levels))
    c_ref[0, 0] = (_to_codes(q) if pack == 1
                   else _pack_block(q, pack, _bits_of(levels)))


def _snap_kernel(c_ref, w_ref, g_ref, l_ref, o_ref, *, levels: int,
                 pack: int = 1):
    g = g_ref[pl.program_id(0), 0]
    c = c_ref[0, 0]
    if pack > 1:
        c = _unpack_block(c, pack, _bits_of(levels))
    c = _from_codes(c)
    lv = _modulus(l_ref, levels)
    q = c + lv * jnp.round((w_ref[0, 0] / g - c) / lv)
    o_ref[0, 0] = q * g


def _decode_kernel(c_ref, ref_ref, s_ref, hr_ref, hc_ref, g_ref, l_ref,
                   o_ref, *, scale: float, levels: int, pack: int = 1):
    s = s_ref[0]
    w = ref_ref[0, 0].astype(jnp.float32) * s
    w = mm_f32(hr_ref[...], w)
    w = mm_f32(w, hc_ref[...]) * scale
    g = g_ref[pl.program_id(0), 0]
    c = c_ref[0, 0]
    if pack > 1:
        c = _unpack_block(c, pack, _bits_of(levels))
    c = _from_codes(c)
    lv = _modulus(l_ref, levels)
    q = c + lv * jnp.round((w / g - c) / lv)
    x = mm_f32(hr_ref[...], q * g)
    x = mm_f32(x, hc_ref[...]) * scale
    o_ref[0, 0] = x * s


# ---------------------------------------------------------------------------
# jit'd wrappers — all take (m, d_pad) message batches + (d_pad,) signs
# ---------------------------------------------------------------------------

def _levels_operand(levels2, m: int):
    """(specs, operands) for an optional per-message levels operand — the
    same (m, 1) SMEM layout the γ scalars use."""
    if levels2 is None:
        return [], []
    return [_SMEM_SPEC], [_scalars(levels2, m)]

@partial(jax.jit, static_argnames=("block", "inverse", "interpret"))
def fused_rotate(x2: jnp.ndarray, signs: jnp.ndarray, *,
                 block: int = DEFAULT_BLOCK, inverse: bool = False,
                 interpret: bool) -> jnp.ndarray:
    """Batched randomized-Hadamard rotation: (m, d_pad) -> (m, d_pad)."""
    m, d_pad = x2.shape
    b, _, r, c, nb = block_geometry(d_pad, block)
    hr, hc = _had(r, c)
    out = pl.pallas_call(
        partial(_rotate_kernel, scale=1.0 / np.sqrt(b), inverse=inverse),
        grid=(m, nb),
        in_specs=[
            pl.BlockSpec((1, 1, r, c), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, r, c), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((r, r), lambda i, j: (0, 0)),
            pl.BlockSpec((c, c), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, r, c), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, nb, r, c), jnp.float32),
        interpret=interpret,
    )(_blk(x2.astype(jnp.float32), nb, r, c), signs.reshape(nb, r, c), hr, hc)
    return out.reshape(m, d_pad)


@partial(jax.jit, static_argnames=("bits", "block", "want_rotated",
                                   "interpret", "pack"))
def fused_encode(x2: jnp.ndarray, signs: jnp.ndarray, u2: jnp.ndarray,
                 gammas: jnp.ndarray, *, bits: int = 8,
                 block: int = DEFAULT_BLOCK, want_rotated: bool = False,
                 interpret: bool, pack: int = 1, levels2=None):
    """Rotate + stochastic-round + wrap in one pass.

    x2: (m, d_pad) padded messages; u2: U(0,1) rounding noise, same shape;
    gammas: (m,) per-message scales; levels2: optional (m,) per-message
    wrap moduli (powers of two <= 2^bits) riding as a levels row. Returns
    codes (m, d_pad) uint32 — or, with ``pack = 8 // bits`` > 1,
    sub-byte-packed codes (m, d_pad // pack) uint8 combined inside the
    kernel — or (rotated, codes) when ``want_rotated`` (one extra
    VMEM->HBM store per block instead of a second full rotation pass
    later).
    """
    m, d_pad = x2.shape
    b, _, r, c, nb = block_geometry(d_pad, block)
    _check_pack(pack, bits, r)
    hr, hc = _had(r, c)
    rp = r // pack
    code_dt = jnp.uint8 if pack > 1 else jnp.uint32
    out_shape = [jax.ShapeDtypeStruct((m, nb, rp, c), code_dt)]
    out_specs = [pl.BlockSpec((1, 1, rp, c), lambda i, j: (i, j, 0, 0))]
    if want_rotated:
        out_shape.append(jax.ShapeDtypeStruct((m, nb, r, c), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, r, c),
                                      lambda i, j: (i, j, 0, 0)))
    l_specs, l_ops = _levels_operand(levels2, m)
    has_levels = levels2 is not None

    def body(x_ref, s_ref, u_ref, hr_ref, hc_ref, g_ref, *rest):
        l_ref = rest[0] if has_levels else None
        outs = rest[1:] if has_levels else rest
        _encode_kernel(x_ref, s_ref, u_ref, hr_ref, hc_ref, g_ref, l_ref,
                       outs[0], outs[1] if want_rotated else None,
                       scale=1.0 / np.sqrt(b), levels=1 << bits,
                       want_rotated=want_rotated, pack=pack)

    res = pl.pallas_call(
        body,
        grid=(m, nb),
        in_specs=[
            pl.BlockSpec((1, 1, r, c), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, r, c), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, 1, r, c), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((r, r), lambda i, j: (0, 0)),
            pl.BlockSpec((c, c), lambda i, j: (0, 0)),
            _SMEM_SPEC,
        ] + l_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(_blk(x2.astype(jnp.float32), nb, r, c), signs.reshape(nb, r, c),
      _blk(u2.astype(jnp.float32), nb, r, c), hr, hc, _scalars(gammas, m),
      *l_ops)
    codes = res[0].reshape(m, d_pad // pack)
    if want_rotated:
        return res[1].reshape(m, d_pad), codes
    return codes


@partial(jax.jit, static_argnames=("bits", "block", "interpret", "pack"))
def quantize_codes(y2: jnp.ndarray, u2: jnp.ndarray, gammas: jnp.ndarray, *,
                   bits: int = 8, block: int = DEFAULT_BLOCK,
                   interpret: bool, pack: int = 1,
                   levels2=None) -> jnp.ndarray:
    """Stochastic-round + wrap of already-rotated coordinates.

    y2: (m, d_pad) ROTATED messages; u2: U(0,1) rounding noise, same shape;
    gammas: (m,) per-message scales; levels2: optional (m,) per-message wrap
    moduli. Elementwise — no Hadamard factors touch the MXU, so encoding a
    cached rotated vector costs no rotation pass. Bit-identical to the
    quantize half of ``fused_encode`` (``pack`` included).
    """
    m, d_pad = y2.shape
    _, _, r, c, nb = block_geometry(d_pad, block)
    _check_pack(pack, bits, r)
    rp = r // pack
    code_dt = jnp.uint8 if pack > 1 else jnp.uint32
    l_specs, l_ops = _levels_operand(levels2, m)
    has_levels = levels2 is not None

    def body(y_ref, u_ref, g_ref, *rest):
        _quantize_kernel(y_ref, u_ref, g_ref,
                         rest[0] if has_levels else None, rest[-1],
                         levels=1 << bits, pack=pack)

    out = pl.pallas_call(
        body,
        grid=(m, nb),
        in_specs=[
            pl.BlockSpec((1, 1, r, c), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, r, c), lambda i, j: (i, j, 0, 0)),
            _SMEM_SPEC,
        ] + l_specs,
        out_specs=pl.BlockSpec((1, 1, rp, c), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, nb, rp, c), code_dt),
        interpret=interpret,
    )(_blk(y2.astype(jnp.float32), nb, r, c),
      _blk(u2.astype(jnp.float32), nb, r, c), _scalars(gammas, m),
      *l_ops)
    return out.reshape(m, d_pad // pack)


@partial(jax.jit, static_argnames=("bits", "block", "interpret", "pack"))
def snap_codes(codes2: jnp.ndarray, wrot2: jnp.ndarray, gammas: jnp.ndarray,
               *, bits: int = 8, block: int = DEFAULT_BLOCK,
               interpret: bool, pack: int = 1,
               levels2=None) -> jnp.ndarray:
    """Positional snap in rotated space: gamma * (c + L round((w/g-c)/L)).

    codes2 (mc, d_pad // pack) and wrot2 (mw, d_pad) broadcast along the
    message axis (mc or mw may be 1); gammas (and the optional per-message
    ``levels2`` moduli) have the codes' batch size. With ``pack > 1`` the
    codes arrive sub-byte packed and are unpacked inline, inside the
    kernel.
    """
    mc, d_padp = codes2.shape
    d_pad = d_padp * pack
    mw = wrot2.shape[0]
    m = max(mc, mw)
    _, _, r, c, nb = block_geometry(d_pad, block)
    _check_pack(pack, bits, r)
    rp = r // pack
    code_dt = jnp.uint8 if pack > 1 else jnp.uint32
    l_specs, l_ops = _levels_operand(levels2, m)
    has_levels = levels2 is not None

    def body(c_ref, w_ref, g_ref, *rest):
        _snap_kernel(c_ref, w_ref, g_ref,
                     rest[0] if has_levels else None, rest[-1],
                     levels=1 << bits, pack=pack)

    out = pl.pallas_call(
        body,
        grid=(m, nb),
        in_specs=[
            _row_spec(mc, rp, c),
            _row_spec(mw, r, c),
            _SMEM_SPEC,
        ] + l_specs,
        out_specs=pl.BlockSpec((1, 1, r, c), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, nb, r, c), jnp.float32),
        interpret=interpret,
    )(_blk(codes2.astype(code_dt), nb, rp, c),
      _blk(wrot2.astype(jnp.float32), nb, r, c), _scalars(gammas, m),
      *l_ops)
    return out.reshape(m, d_pad)


@partial(jax.jit, static_argnames=("bits", "block", "interpret", "pack"))
def fused_decode(codes2: jnp.ndarray, ref2: jnp.ndarray, signs: jnp.ndarray,
                 gammas: jnp.ndarray, *, bits: int = 8,
                 block: int = DEFAULT_BLOCK,
                 interpret: bool, pack: int = 1,
                 levels2=None) -> jnp.ndarray:
    """Full positional decode: rotate ref + snap + inverse rotate, fused.

    codes2 (mc, d_pad // pack) vs references ref2 (mr, d_pad) in ORIGINAL
    space; broadcasts along the message axis; ``levels2`` optionally
    carries per-message snap moduli (the codes' batch size). Packed codes
    (``pack > 1``) are unpacked inline. Returns (max(mc, mr), d_pad) fp32
    in original coordinates (caller unpads with [:, :d]).
    """
    mc = codes2.shape[0]
    mr, d_pad = ref2.shape
    m = max(mc, mr)
    b, _, r, c, nb = block_geometry(d_pad, block)
    _check_pack(pack, bits, r)
    rp = r // pack
    code_dt = jnp.uint8 if pack > 1 else jnp.uint32
    hr, hc = _had(r, c)
    l_specs, l_ops = _levels_operand(levels2, m)
    has_levels = levels2 is not None

    def body(c_ref, ref_ref, s_ref, hr_ref, hc_ref, g_ref, *rest):
        _decode_kernel(c_ref, ref_ref, s_ref, hr_ref, hc_ref, g_ref,
                       rest[0] if has_levels else None, rest[-1],
                       scale=1.0 / np.sqrt(b), levels=1 << bits, pack=pack)

    out = pl.pallas_call(
        body,
        grid=(m, nb),
        in_specs=[
            _row_spec(mc, rp, c),
            _row_spec(mr, r, c),
            pl.BlockSpec((1, r, c), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((r, r), lambda i, j: (0, 0)),
            pl.BlockSpec((c, c), lambda i, j: (0, 0)),
            _SMEM_SPEC,
        ] + l_specs,
        out_specs=pl.BlockSpec((1, 1, r, c), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, nb, r, c), jnp.float32),
        interpret=interpret,
    )(_blk(codes2.astype(code_dt), nb, rp, c),
      _blk(ref2.astype(jnp.float32), nb, r, c), signs.reshape(nb, r, c),
      hr, hc, _scalars(gammas, m), *l_ops)
    return out.reshape(m, d_pad)
