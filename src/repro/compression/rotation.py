"""Randomized Hadamard rotation (the practical lattice quantizer of
Davies et al. [7] is 'a random rotation followed by direct quantization').

The rotation is applied blockwise: the flat vector is padded to a multiple of
``block`` (a power of two) and each block is multiplied by Q = H_b D / sqrt(b)
with D a Rademacher diagonal. We express H_b as H_r ⊗ H_c (b = r*c) so the
transform is two small dense matmuls — on TPU these hit the MXU directly
(a butterfly FWHT is VPU-bound); the Pallas kernel in repro.kernels/hadamard
implements exactly this decomposition. Q is orthogonal and symmetric up to
the sign diagonal, so the inverse is D H_b / sqrt(b).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.geometry import (DEFAULT_BLOCK, block_size, factor,
                                    hadamard_matrix, pad_len)
from repro.utils.spans import NOISE


_LANES = 128


def _dense(shape):
    """The shape to draw ``shape``'s elements at: ``(size // 128, 128)``
    when the size is a multiple of 128, else ``shape`` itself.

    A draw at ``(n,)`` becomes ``(1, n)`` under a one-slot ``vmap``, which
    a TPU tiles ``T(1,128)``: one of a vreg's 8 sublanes, at 8x the time
    of the same draw tiled ``T(8,128)``. jax.random draws each element
    from its row-major index, whatever the shape, so a draw at either
    shape gives the same values bit for bit.
    """
    size = math.prod(shape)
    return shape if size % _LANES else (size // _LANES, _LANES)


def _signs(key, n):
    with jax.named_scope(NOISE):
        return jax.random.rademacher(key, _dense((n,)),
                                     dtype=jnp.float32).reshape(n)


def dither(key, shape):
    """A stochastic quantizer's uniform rounding offsets, in [0, 1)."""
    with jax.named_scope(NOISE):
        return jax.random.uniform(key, _dense(shape),
                                  jnp.float32).reshape(shape)


def rotate(x: jnp.ndarray, key, block: int = DEFAULT_BLOCK,
           inverse: bool = False) -> jnp.ndarray:
    """x: flat (d,) float32 -> rotated, padded to a block multiple.

    forward:  y = (H x*s) / sqrt(b)   (per block)
    inverse:  x = (H y) / sqrt(b) * s
    The caller keeps the padded length; ``unpad`` with [:d].
    """
    d = x.shape[0]
    b = block_size(d, block)
    padded = pad_len(d, block)
    x = jnp.pad(x.astype(jnp.float32), (0, padded - d))
    s = _signs(key, padded)
    r, c = factor(b)
    hr = jnp.asarray(hadamard_matrix(r))
    hc = jnp.asarray(hadamard_matrix(c))
    scale = 1.0 / np.sqrt(b)
    if not inverse:
        x = x * s
    blocks = x.reshape(-1, r, c)
    # (H_r ⊗ H_c) vec(X) == H_r @ X @ H_c^T  (H_c symmetric)
    # full fp32: at default precision a TPU runs one bfloat16 pass
    y = jnp.einsum("ij,bjk,kl->bil", hr, blocks, hc,
                   precision=jax.lax.Precision.HIGHEST) * scale
    y = y.reshape(-1)
    if inverse:
        y = y * s
    return y
