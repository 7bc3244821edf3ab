"""Public jit'd wrappers for the Pallas kernels.

Every wrapper takes ``interpret`` without a default: ``False`` compiles the
kernel for the TPU, ``True`` runs its body through the Pallas interpreter
(any backend; the tests' mode). The pure-jnp oracles live in ref.py and
every kernel is swept against them in tests/test_kernels.py.

``rotate_pallas`` is a drop-in for repro.compression.rotation.rotate with the
Hadamard core executed by the MXU kernel.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.compression.rotation import _signs
from repro.kernels.geometry import DEFAULT_BLOCK, block_size, factor, pad_len
from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.hadamard import hadamard_blocks
from repro.kernels.lattice_quant import lattice_decode, lattice_encode  # noqa: F401


def rotate_pallas(x: jnp.ndarray, key, block: int = DEFAULT_BLOCK,
                  inverse: bool = False, *, interpret: bool):
    """Randomized Hadamard rotation with the Pallas MXU core."""
    d = x.shape[0]
    b = block_size(d, block)
    padded = pad_len(d, block)
    x = jnp.pad(x.astype(jnp.float32), (0, padded - d))
    s = _signs(key, padded)
    r, c = factor(b)
    if not inverse:
        x = x * s
    y = hadamard_blocks(x.reshape(-1, r, c), interpret=interpret).reshape(-1)
    if inverse:
        y = y * s
    return y
