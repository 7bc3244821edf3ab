"""Plain reference of the position-aware lattice exchange (Davies et al.,
as QuAFL uses it): a blockwise randomized Hadamard rotation, stochastic
rounding of the rotated coordinates to a grid of step gamma, wrap modulo
2^bits, and a decode that snaps each code to the grid point nearest the
receiver's own rotated reference before rotating back.

Written from the algorithm's description, with nothing taken from the code
under test: a Sylvester Hadamard matrix, einsums at full float32
precision (unless a control rounds their inputs), and the wrap window of
the lattice (``safety`` times twice the sub-gaussian bound on a rotated
coordinate of the distance hint, over the number of levels), floored at
2^-18 of the same bound on the message's own norm so that fp32 keeps
sub-step resolution.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 16_384
HIGHEST = jax.lax.Precision.HIGHEST


def hadamard(n: int) -> np.ndarray:
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < n:
        h = np.concatenate([np.concatenate([h, h], 1),
                            np.concatenate([h, -h], 1)], 0)
    return h


def block_of(d: int, block: int = BLOCK) -> int:
    """Rotation block: the least power of two at or above min(d, block)."""
    return 1 << max(0, math.ceil(math.log2(min(d, block))))


def padded(d: int, block: int = BLOCK) -> int:
    b = block_of(d, block)
    return -(-d // b) * b


def _factors(b: int):
    k = int(math.log2(b))
    return 1 << ((k + 1) // 2), 1 << (k // 2)


def rotate(x2, signs, *, inverse: bool = False, block: int = BLOCK,
           operand=None):
    """(m, d_pad) -> (m, d_pad): per block Q = H D / sqrt(b) (forward) or
    D H / sqrt(b) (inverse), H = H_r kron H_c. ``operand``, where given,
    rounds the input of each of the two products first (a control)."""
    m, d_pad = x2.shape
    b = block_of(d_pad, block)
    r, c = _factors(b)
    x = x2.astype(jnp.float32)
    if not inverse:
        x = x * signs[None]
    h_r, h_c = jnp.asarray(hadamard(r)), jnp.asarray(hadamard(c))
    x = x.reshape(m * (d_pad // b), r, c)
    if operand is None:
        y = jnp.einsum("ij,njk,kl->nil", h_r, x, h_c, precision=HIGHEST)
    else:
        t = jnp.einsum("njk,kl->njl", operand(x), h_c, precision=HIGHEST)
        y = jnp.einsum("ij,njl->nil", h_r, operand(t), precision=HIGHEST)
    y = (y * np.float32(1.0 / math.sqrt(b))).reshape(m, d_pad)
    return y * signs[None] if inverse else y


def coord_bound(norm, d_pad: int):
    return (jnp.asarray(norm, jnp.float32) / math.sqrt(d_pad)
            * (math.sqrt(2 * math.log(2 * d_pad + 1)) + 2.0))


def gamma(hint, xnorm, d: int, *, bits: int, safety: float = 8.0,
          block: int = BLOCK):
    d_pad = padded(d, block)
    g = jnp.maximum(safety * 2.0 * coord_bound(hint, d_pad) / (1 << bits),
                    1e-12)
    return jnp.maximum(g, coord_bound(xnorm, d_pad) * 2.0 ** -18)


def quantize(y2, u2, g, bits: int):
    """Stochastic rounding of rotated coordinates, wrapped mod 2^bits."""
    return jnp.mod(jnp.floor(y2 / g[:, None] + u2), float(1 << bits))


def snap(codes2, w2, g, bits: int):
    """The grid point with these codes nearest the reference w2."""
    lv = float(1 << bits)
    gg = g[:, None]
    return (codes2 + lv * jnp.round((w2 / gg - codes2) / lv)) * gg


def signs_of(key, d_pad: int):
    return jax.random.rademacher(key, (d_pad,), dtype=jnp.float32)


def pad2(x, d_pad: int):
    x2 = x.reshape(x.shape[0], -1).astype(jnp.float32)
    return jnp.pad(x2, ((0, 0), (0, d_pad - x2.shape[1])))
