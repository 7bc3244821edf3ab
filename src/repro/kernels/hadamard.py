"""Pallas TPU kernel: blocked Hadamard rotation.

TPU adaptation (DESIGN.md §3): the randomized Hadamard rotation used by the
lattice quantizer is the per-round compute hot-spot on the client/server
exchange path (two full passes over the model per round). A butterfly FWHT
is VPU-bound and strides badly through VMEM; instead we express the size-
(r·c) Hadamard as H_r ⊗ H_c and compute H_r @ X @ H_c per (r, c) block —
two 128×128-aligned MXU matmuls per block, VMEM-tiled with one block per
grid step.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.geometry import hadamard_matrix, mm_f32


def _hadamard_kernel(x_ref, hr_ref, hc_ref, o_ref, *, scale: float):
    x = x_ref[0].astype(jnp.float32)
    y = mm_f32(hr_ref[...], x)
    y = mm_f32(y, hc_ref[...])
    o_ref[0] = y * scale


@partial(jax.jit, static_argnames=("interpret",))
def hadamard_blocks(x_blocks: jnp.ndarray, *, interpret: bool):
    """x_blocks: (n, r, c) fp32 -> (H_r X H_c)/sqrt(rc), blockwise.

    H is symmetric, so this is its own inverse-rotation core. Grid over
    blocks; per-step VMEM footprint = r*c + r*r + c*c floats (e.g. 192 KiB
    for 128x128) — well inside the ~16 MiB v5e VMEM budget.
    """
    n, r, c = x_blocks.shape
    hr = jnp.asarray(hadamard_matrix(r))
    hc = jnp.asarray(hadamard_matrix(c))
    scale = 1.0 / np.sqrt(r * c)
    return pl.pallas_call(
        partial(_hadamard_kernel, scale=scale),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, r, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((r, r), lambda i: (0, 0)),
            pl.BlockSpec((c, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, r, c), jnp.float32),
        interpret=interpret,
    )(x_blocks.astype(jnp.float32), hr, hc)
