"""Plain float32 reference of the OLMo-1B-width model as the benchmark runs
it, and the weights both sides start from.

The weights are made from the seed by :func:`weights`, in the flat layout
the system under test takes (``embed/tok``, ``body/0/attn/wq`` ... with a
leading layer axis). The forward pass follows the configuration file:
pre-norm blocks of causal self-attention with rotary embeddings (halves
rotated) and a SwiGLU MLP, non-parametric RMSNorm, no biases, the
embedding tied to the output head and scaled by sqrt(d_model) on input.
Every matrix product goes through ``ein``, which the caller picks: full
float32 (``HIGHEST``) for the reference, float8-rounded inputs for the
control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg):
    d, h = cfg["d_model"], cfg["n_heads"]
    return {"d": d, "h": h, "hd": d // h, "ff": cfg["mlp_ratio"] * d // 2,
            "L": cfg["n_layers"], "V": cfg["embedding_size"]}


def shapes(cfg):
    """{leaf: shape} of the parameter tree."""
    m = dims(cfg)
    d, ff, L = m["d"], m["ff"], m["L"]
    return {"embed/tok": (m["V"], d),
            "body/0/attn/wq": (L, d, d), "body/0/attn/wk": (L, d, d),
            "body/0/attn/wv": (L, d, d), "body/0/attn/wo": (L, d, d),
            "body/0/mlp/w_gate": (L, d, ff), "body/0/mlp/w_up": (L, d, ff),
            "body/0/mlp/w_down": (L, ff, d)}


def weights(cfg, key):
    """Normal weights scaled by 1/sqrt(fan in), one key per leaf."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        fan_in = shape[-1] if name == "embed/tok" else shape[-2]
        out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32)
                     / np.float32(math.sqrt(fan_in)))
    return out


def matmul_params(cfg) -> int:
    """Weights that enter a matrix product per token: the blocks' and the
    tied output head's (the input lookup is no product)."""
    return sum(int(np.prod(s)) for s in shapes(cfg).values())


def flops_per_token(cfg, seq: int) -> float:
    """Forward and backward: 6 per matmul weight, plus 12 L d T for the
    attention scores and their weighted sum (causal mask not discounted,
    recomputation not counted)."""
    m = dims(cfg)
    return 6.0 * matmul_params(cfg) + 12.0 * m["L"] * m["d"] * seq


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta):
    t, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) * 2.0 / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def row_loss(cfg, params, toks, ein):
    """Summed next-token cross-entropy of one (T,) row of tokens."""
    m = dims(cfg)
    eps = cfg["layer_norm_eps"]
    t = toks.shape[0]
    emb = params["embed/tok"]
    x = emb[toks] * np.float32(math.sqrt(m["d"]))
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        h = _rms(x, eps)
        q, k, v = (ein("td,de->te", h, p[f"attn/{w}"]).reshape(
            t, m["h"], m["hd"]) for w in ("wq", "wk", "wv"))
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        s = ein("thd,shd->hts", q, k) * np.float32(1.0 / math.sqrt(m["hd"]))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = ein("hts,shd->thd", jax.nn.softmax(s, -1), v).reshape(t, m["d"])
        x = x + ein("te,ed->td", o, p["attn/wo"])
        h = _rms(x, eps)
        a = jax.nn.silu(ein("td,df->tf", h, p["mlp/w_gate"]))
        x = x + ein("tf,fd->td", a * ein("td,df->tf", h, p["mlp/w_up"]),
                    p["mlp/w_down"])
        return x, None

    body = {k[len("body/0/"):]: v for k, v in params.items()
            if k.startswith("body/0/")}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, body)
    logits = ein("td,vd->tv", _rms(x, eps), emb)[:-1]
    tgt = jnp.take_along_axis(logits, toks[1:, None], -1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - tgt)
