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
                        leaf-wise transport and the per-message codec API)

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

All kernels run over a ``(m, nb // g)`` grid — ``m`` messages by steps of
``g`` Hadamard blocks. A grid step has a fixed cost (~0.35 us on a v5e)
that a 128 x 128 block's own work (~0.3-0.5 us) does not hide, so each
step takes the most blocks that divide ``nb`` and fit the scoped VMEM
(:func:`_blocks_per_step`, from the shapes and dtypes alone). A step
applies the left Hadamard factor block by block and the right one to all
``g r`` rows at once, so every block's arithmetic is that of a one-block
step, bit for bit; the two small Hadamard factors stay resident in VMEM.
Batched operands broadcast along ``m`` through the block index maps (no
HBM materialization of the broadcast). Per-message scales ``gamma`` ride
whole in SMEM as an (m, 1) column and each grid step reads
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
from typing import NamedTuple, Optional

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
    """(g, r, c) integral float codes -> (g, r//pack, c) uint8, packed
    along each block's sublanes. Shifts and sums run in int32: Mosaic
    reduces no unsigned integers, and a packed byte is < 256."""
    g, r, c = q.shape
    qi = q.astype(jnp.int32).reshape(g, r // pack, pack, c)
    shifts = (jnp.arange(pack, dtype=jnp.int32) * bits
              ).reshape(1, 1, pack, 1)
    return jnp.sum(qi << shifts, axis=2).astype(jnp.uint8)


def _unpack_block(p, pack: int, bits: int):
    """(g, r//pack, c) packed uint8 -> (g, r, c) uint32 codes."""
    g, rp, c = p.shape
    pi = p.astype(jnp.uint32)[:, :, None, :]
    shifts = (jnp.arange(pack, dtype=jnp.uint32) * bits
              ).reshape(1, 1, pack, 1)
    mask = jnp.uint32((1 << bits) - 1)
    return ((pi >> shifts) & mask).reshape(g, rp * pack, c)


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


# ---------------------------------------------------------------------------
# the grouped grid
# ---------------------------------------------------------------------------

# A TPU core's default scoped VMEM (16 MiB on a v5e): a grid step's
# double-buffered operand and output blocks and its f32 intermediates must
# fit in it
_SCOPED_VMEM = 16 << 20

# trace-time sinks (``OpBudget``s) of the grid counters; see
# ``repro.analysis.opbudget.exchange_grid_counts``
GRID_SINKS: list = []
GRID_STEPS = "exchange_grid_steps"
GRID_BLOCKS = "exchange_blocks"


class _Grid(NamedTuple):
    """One call's grid: ``m`` messages by ``nb // g`` steps of ``g``
    Hadamard blocks of (r, c). Hashable, so it rides as a static argument
    of the jitted launch."""
    m: int
    nb: int
    r: int
    c: int
    g: int

    @property
    def scale(self) -> float:
        return 1.0 / np.sqrt(self.r * self.c)

    def steps(self):
        return (self.m, self.nb // self.g)

    def spec(self, rows: int, broadcast: bool = False):
        """BlockSpec of a (m_or_1, nb, rows, c) message operand, read
        along the message axis or broadcast over it."""
        if broadcast:
            return pl.BlockSpec((1, self.g, rows, self.c),
                                lambda i, j: (0, j, 0, 0))
        return pl.BlockSpec((1, self.g, rows, self.c),
                            lambda i, j: (i, j, 0, 0))

    def signs_spec(self):
        return pl.BlockSpec((self.g, self.r, self.c), lambda i, j: (j, 0, 0))

    def factor_specs(self):
        return [pl.BlockSpec((self.r, self.r), lambda i, j: (0, 0)),
                pl.BlockSpec((self.c, self.c), lambda i, j: (0, 0))]

    def blocks(self, x2, dtype, rows: Optional[int] = None):
        return x2.astype(dtype).reshape(x2.shape[0], self.nb,
                                        rows or self.r, self.c)


def _blocks_per_step(nb: int, r: int, c: int, streamed: float,
                     temps: int) -> int:
    """Hadamard blocks a grid step takes: the largest power of two that
    divides ``nb`` and whose step fits the scoped VMEM.

    ``streamed`` is the bytes per element of the step's operand and output
    blocks, each double-buffered by the pipeline; ``temps`` counts the
    step's block-sized f32 intermediates. Each kernel's ``temps`` is what
    the v5e compiler reports beyond the buffers, in f32 blocks at r = c =
    128, rounded up: rotate and encode 3.3, quantize 2 (packed 3.8), snap
    3.5 (packed 5), decode 4.4 (packed 7.9). The Hadamard factors stay
    resident. A step of g blocks collapses them to (g r, c) rows for the
    right factor, free only where r is a multiple of the 8 sublanes, so
    other r keep one block a step.
    """
    if r % 8:
        return 1
    fixed = 2 * 4 * (r * r + c * c)
    per_block = r * c * (2 * streamed + 4 * temps)
    g = 1
    while nb % (2 * g) == 0 and fixed + 2 * g * per_block <= _SCOPED_VMEM:
        g *= 2
    return g


def _grid(m: int, d_pad: int, block: int, streamed: float,
          temps: int) -> _Grid:
    """Plan a call's grid and add it to the active grid counters. Runs
    outside the jitted launch, whose trace cache would skip a count made
    inside it."""
    _, _, r, c, nb = block_geometry(d_pad, block)
    grid = _Grid(m, nb, r, c, _blocks_per_step(nb, r, c, streamed, temps))
    for sink in GRID_SINKS:
        sink.add(GRID_STEPS, m * nb // grid.g)
        sink.add(GRID_BLOCKS, m * nb)
    return grid


def _code_bytes(pack: int) -> float:
    """Bytes a code takes in its block: uint32, or ``pack`` to a byte."""
    return 4.0 if pack == 1 else 1.0 / pack


# ---------------------------------------------------------------------------
# kernel bodies: refs hold (1, g, r, c) message blocks, (g, r, c) signs
# ---------------------------------------------------------------------------

def _rot(hr, hc, x, scale: float):
    """H_r @ X @ H_c * scale for each (r, c) block X of x (g, r, c): the
    left factor a block at a time, the right one over all g r rows at
    once. Each output element sums the same products in the same order
    as a one-block step, so g changes no bit."""
    g, r, c = x.shape
    y = jnp.stack([mm_f32(hr, x[k]) for k in range(g)])
    return (mm_f32(y.reshape(g * r, c), hc) * scale).reshape(g, r, c)


def _rotate_kernel(x_ref, s_ref, hr_ref, hc_ref, o_ref, *, scale: float,
                   inverse: bool):
    x = x_ref[0].astype(jnp.float32)
    if not inverse:
        x = x * s_ref[...]
    y = _rot(hr_ref[...], hc_ref[...], x, scale)
    if inverse:
        y = y * s_ref[...]
    o_ref[0] = y


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


def _store_codes(c_ref, q, levels: int, pack: int):
    c_ref[0] = (_to_codes(q) if pack == 1
                else _pack_block(q, pack, _bits_of(levels)))


def _load_codes(c_ref, levels: int, pack: int):
    c = c_ref[0]
    if pack > 1:
        c = _unpack_block(c, pack, _bits_of(levels))
    return _from_codes(c)


def _encode_kernel(x_ref, s_ref, u_ref, hr_ref, hc_ref, g_ref, l_ref, c_ref,
                   y_ref, *, scale: float, levels: int, want_rotated: bool,
                   pack: int = 1):
    x = x_ref[0].astype(jnp.float32) * s_ref[...]
    y = _rot(hr_ref[...], hc_ref[...], x, scale)
    g = g_ref[pl.program_id(0), 0]
    q = jnp.floor(y / g + u_ref[0])
    _store_codes(c_ref, jnp.mod(q, _modulus(l_ref, levels)), levels, pack)
    if want_rotated:
        y_ref[0] = y


def _quantize_kernel(y_ref, u_ref, g_ref, l_ref, c_ref, *, levels: int,
                     pack: int = 1):
    g = g_ref[pl.program_id(0), 0]
    q = jnp.floor(y_ref[0].astype(jnp.float32) / g + u_ref[0])
    _store_codes(c_ref, jnp.mod(q, _modulus(l_ref, levels)), levels, pack)


def _snap(c, w, g, lv):
    """The representative of codes c nearest w / g, times g."""
    return (c + lv * jnp.round((w / g - c) / lv)) * g


def _snap_kernel(c_ref, w_ref, g_ref, l_ref, o_ref, *, levels: int,
                 pack: int = 1):
    g = g_ref[pl.program_id(0), 0]
    o_ref[0] = _snap(_load_codes(c_ref, levels, pack), w_ref[0], g,
                     _modulus(l_ref, levels))


def _decode_kernel(c_ref, ref_ref, s_ref, hr_ref, hc_ref, g_ref, l_ref,
                   o_ref, *, scale: float, levels: int, pack: int = 1):
    s = s_ref[...]
    hr, hc = hr_ref[...], hc_ref[...]
    w = _rot(hr, hc, ref_ref[0].astype(jnp.float32) * s, scale)
    g = g_ref[pl.program_id(0), 0]
    q = _snap(_load_codes(c_ref, levels, pack), w, g,
              _modulus(l_ref, levels))
    o_ref[0] = _rot(hr, hc, q, scale) * s


# ---------------------------------------------------------------------------
# wrappers — all take (m, d_pad) message batches + (d_pad,) signs. Each
# plans its grid in Python, then runs a jitted launch with the grid static.
# ---------------------------------------------------------------------------

def _levels_operand(levels2, m: int):
    """(specs, operands) for an optional per-message levels operand — the
    same (m, 1) SMEM layout the γ scalars use."""
    if levels2 is None:
        return [], []
    return [_SMEM_SPEC], [_scalars(levels2, m)]


def _code_dtype(pack: int):
    return jnp.uint8 if pack > 1 else jnp.uint32


def fused_rotate(x2: jnp.ndarray, signs: jnp.ndarray, *,
                 block: int = DEFAULT_BLOCK, inverse: bool = False,
                 interpret: bool) -> jnp.ndarray:
    """Batched randomized-Hadamard rotation: (m, d_pad) -> (m, d_pad)."""
    grid = _grid(x2.shape[0], x2.shape[1], block, streamed=12, temps=4)
    return _rotate_launch(x2, signs, grid=grid, inverse=inverse,
                          interpret=interpret)


@partial(jax.jit, static_argnames=("grid", "inverse", "interpret"))
def _rotate_launch(x2, signs, *, grid: _Grid, inverse: bool,
                   interpret: bool):
    m, nb, r, c, _ = grid
    hr, hc = _had(r, c)
    out = pl.pallas_call(
        partial(_rotate_kernel, scale=grid.scale, inverse=inverse),
        grid=grid.steps(),
        in_specs=[grid.spec(r), grid.signs_spec(), *grid.factor_specs()],
        out_specs=grid.spec(r),
        out_shape=jax.ShapeDtypeStruct((m, nb, r, c), jnp.float32),
        interpret=interpret,
    )(grid.blocks(x2, jnp.float32), signs.reshape(nb, r, c), hr, hc)
    return out.reshape(x2.shape)


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
    streamed = 12 + _code_bytes(pack) + 4 * want_rotated
    grid = _grid(x2.shape[0], x2.shape[1], block, streamed, temps=4)
    _check_pack(pack, bits, grid.r)
    return _encode_launch(x2, signs, u2, gammas, levels2, grid=grid,
                          bits=bits, want_rotated=want_rotated,
                          interpret=interpret, pack=pack)


@partial(jax.jit, static_argnames=("grid", "bits", "want_rotated",
                                   "interpret", "pack"))
def _encode_launch(x2, signs, u2, gammas, levels2, *, grid: _Grid, bits: int,
                   want_rotated: bool, interpret: bool, pack: int):
    m, nb, r, c, _ = grid
    hr, hc = _had(r, c)
    rp = r // pack
    out_shape = [jax.ShapeDtypeStruct((m, nb, rp, c), _code_dtype(pack))]
    out_specs = [grid.spec(rp)]
    if want_rotated:
        out_shape.append(jax.ShapeDtypeStruct((m, nb, r, c), jnp.float32))
        out_specs.append(grid.spec(r))
    l_specs, l_ops = _levels_operand(levels2, m)
    has_levels = levels2 is not None

    def body(x_ref, s_ref, u_ref, hr_ref, hc_ref, g_ref, *rest):
        l_ref = rest[0] if has_levels else None
        outs = rest[1:] if has_levels else rest
        _encode_kernel(x_ref, s_ref, u_ref, hr_ref, hc_ref, g_ref, l_ref,
                       outs[0], outs[1] if want_rotated else None,
                       scale=grid.scale, levels=1 << bits,
                       want_rotated=want_rotated, pack=pack)

    res = pl.pallas_call(
        body,
        grid=grid.steps(),
        in_specs=[grid.spec(r), grid.signs_spec(), grid.spec(r),
                  *grid.factor_specs(), _SMEM_SPEC] + l_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(grid.blocks(x2, jnp.float32), signs.reshape(nb, r, c),
      grid.blocks(u2, jnp.float32), hr, hc, _scalars(gammas, m), *l_ops)
    codes = res[0].reshape(m, nb * rp * c)
    if want_rotated:
        return res[1].reshape(m, nb * r * c), codes
    return codes


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
    grid = _grid(y2.shape[0], y2.shape[1], block, 8 + _code_bytes(pack),
                 temps=3 if pack == 1 else 5)
    _check_pack(pack, bits, grid.r)
    return _quantize_launch(y2, u2, gammas, levels2, grid=grid, bits=bits,
                            interpret=interpret, pack=pack)


@partial(jax.jit, static_argnames=("grid", "bits", "interpret", "pack"))
def _quantize_launch(y2, u2, gammas, levels2, *, grid: _Grid, bits: int,
                     interpret: bool, pack: int):
    m, nb, r, c, _ = grid
    rp = r // pack
    l_specs, l_ops = _levels_operand(levels2, m)
    has_levels = levels2 is not None

    def body(y_ref, u_ref, g_ref, *rest):
        _quantize_kernel(y_ref, u_ref, g_ref,
                         rest[0] if has_levels else None, rest[-1],
                         levels=1 << bits, pack=pack)

    out = pl.pallas_call(
        body,
        grid=grid.steps(),
        in_specs=[grid.spec(r), grid.spec(r), _SMEM_SPEC] + l_specs,
        out_specs=grid.spec(rp),
        out_shape=jax.ShapeDtypeStruct((m, nb, rp, c), _code_dtype(pack)),
        interpret=interpret,
    )(grid.blocks(y2, jnp.float32), grid.blocks(u2, jnp.float32),
      _scalars(gammas, m), *l_ops)
    return out.reshape(m, nb * rp * c)


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
    m = max(codes2.shape[0], wrot2.shape[0])
    grid = _grid(m, wrot2.shape[1], block, 8 + _code_bytes(pack),
                 temps=4 if pack == 1 else 6)
    _check_pack(pack, bits, grid.r)
    return _snap_launch(codes2, wrot2, gammas, levels2, grid=grid, bits=bits,
                        interpret=interpret, pack=pack)


@partial(jax.jit, static_argnames=("grid", "bits", "interpret", "pack"))
def _snap_launch(codes2, wrot2, gammas, levels2, *, grid: _Grid, bits: int,
                 interpret: bool, pack: int):
    m, nb, r, c, _ = grid
    rp = r // pack
    l_specs, l_ops = _levels_operand(levels2, m)
    has_levels = levels2 is not None

    def body(c_ref, w_ref, g_ref, *rest):
        _snap_kernel(c_ref, w_ref, g_ref,
                     rest[0] if has_levels else None, rest[-1],
                     levels=1 << bits, pack=pack)

    out = pl.pallas_call(
        body,
        grid=grid.steps(),
        in_specs=[grid.spec(rp, codes2.shape[0] == 1),
                  grid.spec(r, wrot2.shape[0] == 1), _SMEM_SPEC] + l_specs,
        out_specs=grid.spec(r),
        out_shape=jax.ShapeDtypeStruct((m, nb, r, c), jnp.float32),
        interpret=interpret,
    )(grid.blocks(codes2, _code_dtype(pack), rp),
      grid.blocks(wrot2, jnp.float32), _scalars(gammas, m), *l_ops)
    return out.reshape(m, nb * r * c)


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
    m = max(codes2.shape[0], ref2.shape[0])
    grid = _grid(m, ref2.shape[1], block, 12 + _code_bytes(pack),
                 temps=5 if pack == 1 else 9)
    _check_pack(pack, bits, grid.r)
    return _decode_launch(codes2, ref2, signs, gammas, levels2, grid=grid,
                          bits=bits, interpret=interpret, pack=pack)


@partial(jax.jit, static_argnames=("grid", "bits", "interpret", "pack"))
def _decode_launch(codes2, ref2, signs, gammas, levels2, *, grid: _Grid,
                   bits: int, interpret: bool, pack: int):
    m, nb, r, c, _ = grid
    rp = r // pack
    hr, hc = _had(r, c)
    l_specs, l_ops = _levels_operand(levels2, m)
    has_levels = levels2 is not None

    def body(c_ref, ref_ref, s_ref, hr_ref, hc_ref, g_ref, *rest):
        _decode_kernel(c_ref, ref_ref, s_ref, hr_ref, hc_ref, g_ref,
                       rest[0] if has_levels else None, rest[-1],
                       scale=grid.scale, levels=1 << bits, pack=pack)

    out = pl.pallas_call(
        body,
        grid=grid.steps(),
        in_specs=[grid.spec(rp, codes2.shape[0] == 1),
                  grid.spec(r, ref2.shape[0] == 1), grid.signs_spec(),
                  *grid.factor_specs(), _SMEM_SPEC] + l_specs,
        out_specs=grid.spec(r),
        out_shape=jax.ShapeDtypeStruct((m, nb, r, c), jnp.float32),
        interpret=interpret,
    )(grid.blocks(codes2, _code_dtype(pack), rp),
      grid.blocks(ref2, jnp.float32), signs.reshape(nb, r, c), hr, hc,
      _scalars(gammas, m), *l_ops)
    return out.reshape(m, nb * r * c)
