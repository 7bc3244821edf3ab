"""Pallas TPU kernels: fused lattice-quantizer encode / decode.

encode: codes = floor(y/γ + u) mod 2^b       (stochastic round + wrap)
decode: x̂    = γ·(codes + 2^b·round((w/γ − codes)/2^b))   (positional snap)

Both are elementwise streams over the (padded) rotated vector: VMEM-tiled
(8, 128)-aligned rows, one tile per grid step. Fusing scale, round, wrap and
snap into one pass halves the HBM traffic versus the jnp composition (which
materializes y/γ and the rounded intermediate).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUB = 8
TILE = LANE * SUB * 8  # elements per grid step


def _encode_kernel(y_ref, u_ref, g_ref, o_ref, *, levels: int):
    g = g_ref[0]
    q = jnp.floor(y_ref[...] / g + u_ref[...])
    # float -> uint32 through int32: Mosaic has no direct convert
    o_ref[...] = jnp.mod(q, float(levels)).astype(jnp.int32).astype(jnp.uint32)


def _decode_kernel(c_ref, w_ref, g_ref, o_ref, *, levels: int):
    g = g_ref[0]
    c = c_ref[...].astype(jnp.int32).astype(jnp.float32)
    q = c + levels * jnp.round((w_ref[...] / g - c) / levels)
    o_ref[...] = q * g


def _tiles(d: int):
    assert d % (SUB * LANE) == 0, d
    rows = d // LANE
    block_rows = min(rows, SUB * 8)
    while rows % block_rows:
        block_rows //= 2
    return rows, block_rows


@partial(jax.jit, static_argnames=("bits", "interpret"))
def lattice_encode(y: jnp.ndarray, u: jnp.ndarray, gamma, *, bits: int = 8,
                   interpret: bool):
    """y: rotated coords (d,), d % 1024 == 0; u: U(0,1) noise (d,)."""
    d = y.shape[0]
    rows, br = _tiles(d)
    y2 = y.reshape(rows, LANE).astype(jnp.float32)
    u2 = u.reshape(rows, LANE).astype(jnp.float32)
    g = jnp.asarray(gamma, jnp.float32).reshape(1)
    out = pl.pallas_call(
        partial(_encode_kernel, levels=1 << bits),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((br, LANE), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((br, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.uint32),
        interpret=interpret,
    )(y2, u2, g)
    return out.reshape(d)


@partial(jax.jit, static_argnames=("bits", "interpret"))
def lattice_decode(codes: jnp.ndarray, w: jnp.ndarray, gamma, *,
                   bits: int = 8, interpret: bool):
    """codes: (d,) uint; w: rotated reference (d,)."""
    d = codes.shape[0]
    rows, br = _tiles(d)
    c2 = codes.reshape(rows, LANE).astype(jnp.uint32)
    w2 = w.reshape(rows, LANE).astype(jnp.float32)
    g = jnp.asarray(gamma, jnp.float32).reshape(1)
    out = pl.pallas_call(
        partial(_decode_kernel, levels=1 << bits),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, LANE), lambda i: (i, 0)),
                  pl.BlockSpec((br, LANE), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((br, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=interpret,
    )(c2, w2, g)
    return out.reshape(d)
