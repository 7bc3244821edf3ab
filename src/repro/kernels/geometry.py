"""Hadamard block geometry and the fp32 matmul of the rotation.

Shared by the jnp rotation (:mod:`repro.compression.rotation`) and the
Pallas kernels. It imports nothing from ``repro``, so the compression and
kernel packages can each import it first.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BLOCK = 16_384  # 128 x 128


@lru_cache(maxsize=None)
def hadamard_matrix(n: int) -> np.ndarray:
    """Sylvester construction; n must be a power of two."""
    assert n & (n - 1) == 0, n
    h = np.array([[1.0]], dtype=np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h


def factor(block: int):
    """(r, c) with r * c == block, r >= c, both powers of two."""
    k = int(np.log2(block))
    r = 1 << ((k + 1) // 2)
    c = 1 << (k // 2)
    assert r * c == block
    return r, c


def block_size(d: int, block: int) -> int:
    """The rotation block of a length-d vector: the smallest power of two
    >= min(d, block)."""
    b = 1
    while b < min(d, block):
        b <<= 1
    return b


def pad_len(d: int, block: int = DEFAULT_BLOCK) -> int:
    b = block_size(d, block)
    return int(np.ceil(d / b)) * b


def mm_f32(a, b):
    """fp32 matmul at full precision. The default would run one bfloat16
    pass on a TPU's MXU (~3 significant digits), more error than the
    lattice decode tolerates: its γ floor assumes fp32 coordinates."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
