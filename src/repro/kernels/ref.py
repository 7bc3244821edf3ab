"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.geometry import hadamard_matrix


def hadamard_ref(x_blocks: jnp.ndarray) -> jnp.ndarray:
    """x_blocks: (n, r, c) -> (H_r @ X @ H_c) / sqrt(r*c). H is symmetric."""
    n, r, c = x_blocks.shape
    hr = jnp.asarray(hadamard_matrix(r))
    hc = jnp.asarray(hadamard_matrix(c))
    scale = 1.0 / np.sqrt(r * c)
    return jnp.einsum("ij,bjk,kl->bil", hr, x_blocks.astype(jnp.float32),
                      hc) * scale


def lattice_encode_ref(y: jnp.ndarray, u: jnp.ndarray, gamma, bits: int):
    """y: rotated coords; u: U(0,1) rounding noise. codes in [0, 2^bits)."""
    levels = 1 << bits
    q = jnp.floor(y.astype(jnp.float32) / gamma + u)
    return jnp.mod(q, levels).astype(jnp.uint32)


def lattice_decode_ref(codes: jnp.ndarray, w: jnp.ndarray, gamma, bits: int):
    """w: rotated reference. Snap to the representative nearest w/gamma."""
    levels = 1 << bits
    c = codes.astype(jnp.float32)
    q = c + levels * jnp.round((w.astype(jnp.float32) / gamma - c) / levels)
    return q * gamma


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0):
    """q: (b, tq, h, dh); k, v: (b, tk, kv, dh). GQA by head repetition."""
    b, tq, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, tq, kvh, g, dh).astype(jnp.float32)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k.astype(jnp.float32))
    scores = scores / np.sqrt(dh)
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    tk = k.shape[1]
    qpos = jnp.arange(tq)[:, None]
    kpos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    return out.reshape(b, tq, h, dh).astype(q.dtype)
