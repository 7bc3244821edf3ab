"""Shared building blocks: norms, RoPE, dense MLPs, embeddings."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, weight=None, eps: float = 1e-6):
    """RMSNorm; weight=None gives the non-parametric form (OLMo-style)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    if weight is not None:
        x = x * (1.0 + weight.astype(jnp.float32))
    return x.astype(dtype)


def init_norm(ctx, cfg, name: str, dim: int):
    if cfg.nonparametric_ln:
        return None
    ctx.param(f"{name}/scale", (dim,), (None,), init="zeros")


def apply_norm(cfg, p, name: str, x):
    if cfg.nonparametric_ln:
        return rms_norm(x, None)
    return rms_norm(x, p[f"{name}/scale"])


def softcap(x, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor (DeepSeek-V2 ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_blend(freqs, head_dim: int, theta: float, s):
    """DeepSeek-V2's YaRN frequencies: the interpolated (freqs / factor)
    below the correction range found from ``beta_fast``/``beta_slow``, the
    original above it, a linear ramp between."""
    def dim_of(rotations):
        return (head_dim * math.log(s.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(dim_of(s.beta_fast)), 0)
    hi = min(math.ceil(dim_of(s.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - lo)
                   / np.float32(hi - lo), 0, 1)
    extra = 1.0 - ramp
    return ((freqs / np.float32(s.factor)) * (1.0 - extra)
            + freqs * extra).astype(np.float32)


def rope_freqs(head_dim: int, theta: float, scaling=None):
    half = head_dim // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) * 2.0 / head_dim))
    if scaling is not None:
        freqs = _yarn_blend(freqs, head_dim, theta, scaling)
    return freqs


def apply_rope(x, positions, theta: float, scaling=None):
    """x: (..., t, heads, head_dim); positions: (..., t) int32. ``scaling``
    (a ``YarnScaling``) blends the frequencies and scales cos and sin by
    mscale(mscale) / mscale(mscale_all_dim)."""
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(head_dim, theta, scaling))  # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs     # (..., t, half)
    cos = jnp.cos(ang)[..., None, :]                           # (..., t, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * np.float32(m), sin * np.float32(m)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense (SwiGLU) MLP
# ---------------------------------------------------------------------------

def init_mlp(ctx, d_model: int, d_ff: int):
    ctx.param("w_gate", (d_model, d_ff), ("embed", "mlp"))
    ctx.param("w_up", (d_model, d_ff), ("embed", "mlp"))
    ctx.param("w_down", (d_ff, d_model), ("mlp", "embed"))


def apply_mlp(p, x, prefix: str = ""):
    pre = prefix + "/" if prefix else ""
    h = jax.nn.silu(x @ p[f"{pre}w_gate"].astype(x.dtype)) \
        * (x @ p[f"{pre}w_up"].astype(x.dtype))
    return h @ p[f"{pre}w_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings / LM head
# ---------------------------------------------------------------------------

def init_embed(ctx, cfg):
    ctx.param("embed/tok", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
              scale=1.0 / np.sqrt(cfg.d_model))
    if not cfg.tie_embeddings:
        ctx.param("lm_head/w", (cfg.d_model, cfg.vocab_size),
                  ("embed", "vocab"))


def embed_tokens(cfg, p, tokens):
    x = jnp.take(p["embed/tok"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.tie_embeddings:
        # tied-head models (gemma) scale the embedding stream
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


def lm_logits(cfg, p, x):
    if cfg.tie_embeddings:
        w = p["embed/tok"].astype(x.dtype)
        logits = x @ w.T
    else:
        logits = x @ p["lm_head/w"].astype(x.dtype)
    return softcap(logits.astype(jnp.float32), cfg.logit_softcap)
