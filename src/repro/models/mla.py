"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Prefill uses the expanded (naive) form with query chunking. Decode uses the
ABSORBED form: W_UK is folded into the query and W_UV into the output so the
per-step attention runs directly over the compressed (kv_lora + rope) cache —
this is the TPU-friendly formulation (naive decode would re-expand the whole
cache every step: ~60 TFLOP/token for the 236B config).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope, rms_norm, yarn_mscale
from repro.utils.spans import MLA

NEG_INF = -1e30


def init_mla(ctx, cfg):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank:
        ctx.param("wq_a", (d, m.q_lora_rank), ("embed", "lora"))
        ctx.param("q_norm/scale", (m.q_lora_rank,), (None,), init="zeros")
        ctx.param("wq_b", (m.q_lora_rank, h * qd), ("lora", "q_flat"))
    else:
        ctx.param("wq", (d, h * qd), ("embed", "q_flat"))
    ctx.param("wkv_a", (d, m.kv_lora_rank + m.qk_rope_dim), ("embed", "lora"))
    ctx.param("kv_norm/scale", (m.kv_lora_rank,), (None,), init="zeros")
    ctx.param("wkv_b", (m.kv_lora_rank, h * (m.qk_nope_dim + m.v_head_dim)),
              ("lora", "q_flat"))
    ctx.param("wo", (h * m.v_head_dim, d), ("q_flat", "embed"))


def _project_q(cfg, p, x, positions, pre):
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank:
        ql = rms_norm(x @ p[f"{pre}wq_a"].astype(x.dtype),
                      p[f"{pre}q_norm/scale"])
        q = ql @ p[f"{pre}wq_b"].astype(x.dtype)
    else:
        q = x @ p[f"{pre}wq"].astype(x.dtype)
    q = q.reshape(b, t, h, qd)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope


def _softmax_scale(cfg):
    """1/sqrt(q head dim), times YaRN's mscale(mscale_all_dim) squared
    where the rope is scaled (DeepSeek-V2's ``softmax_scale``)."""
    m, s = cfg.mla, cfg.rope_scaling
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if s is not None and s.mscale_all_dim:
        scale *= yarn_mscale(s.factor, s.mscale_all_dim) ** 2
    return scale


def _project_kv_latent(cfg, p, x, positions, pre):
    m = cfg.mla
    kv = x @ p[f"{pre}wkv_a"].astype(x.dtype)
    c_kv = rms_norm(kv[..., :m.kv_lora_rank], p[f"{pre}kv_norm/scale"])
    k_rope = kv[..., m.kv_lora_rank:]           # (b, t, rope_dim), head-shared
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)[..., 0, :]
    return c_kv, k_rope


def mla_prefill(cfg, p, x, positions, prefix: str = "", cache=None,
                write_pos=0):
    """Expanded-form causal MLA over the full sequence."""
    with jax.named_scope(MLA):
        return _mla_prefill(cfg, p, x, positions, prefix, cache, write_pos)


def _mla_prefill(cfg, p, x, positions, prefix, cache, write_pos):
    pre = prefix + "/" if prefix else ""
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _project_q(cfg, p, x, positions, pre)
    c_kv, k_rope = _project_kv_latent(cfg, p, x, positions, pre)
    wkv_b = p[f"{pre}wkv_b"].astype(x.dtype).reshape(
        m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
    k_nope = jnp.einsum("btk,khn->bthn", c_kv, wkv_b[..., :m.qk_nope_dim])
    v = jnp.einsum("btk,khv->bthv", c_kv, wkv_b[..., m.qk_nope_dim:])
    scale = _softmax_scale(cfg)

    cq = 1024 if (t % 1024 == 0 and t > 1024) else t
    if cq == t:
        pos = jnp.arange(t)
        mask = pos[:, None] >= pos[None, :]
        out = _mla_sdpa(q_nope, q_rope, k_nope, k_rope, v, mask, scale)
    else:
        def step(_, idx):
            c0 = idx * cq
            qn = jax.lax.dynamic_slice_in_dim(q_nope, c0, cq, axis=1)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, c0, cq, axis=1)
            qpos = c0 + jnp.arange(cq)
            mask = qpos[:, None] >= jnp.arange(t)[None, :]
            return None, _mla_sdpa(qn, qr, k_nope, k_rope, v, mask, scale)
        _, outs = jax.lax.scan(step, None, jnp.arange(t // cq))
        out = jnp.moveaxis(outs, 0, 1).reshape(b, t, h, m.v_head_dim)

    new_cache = None
    if cache is not None:
        s = cache["c_kv"].shape[1]
        if t >= s:
            new_cache = {"c_kv": c_kv[:, -s:], "k_rope": k_rope[:, -s:]}
        else:
            new_cache = {
                "c_kv": jax.lax.dynamic_update_slice_in_dim(
                    cache["c_kv"], c_kv.astype(cache["c_kv"].dtype),
                    write_pos, axis=1),
                "k_rope": jax.lax.dynamic_update_slice_in_dim(
                    cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
                    write_pos, axis=1)}
    out = out.reshape(b, t, -1) @ p[f"{pre}wo"].astype(x.dtype)
    return out, new_cache


def _mla_sdpa(q_nope, q_rope, k_nope, k_rope, v, mask, scale):
    from repro.models.attention import _score_dtype
    sd = _score_dtype(q_nope)
    scores = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                         preferred_element_type=sd).astype(jnp.float32)
              + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope,
                           preferred_element_type=sd).astype(jnp.float32)
              ) * scale
    scores = jnp.where(mask[None, None] if mask.ndim == 2 else mask[:, None],
                       scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshv->bthv", probs, v.astype(jnp.float32))
    return out.astype(q_nope.dtype)


def init_mla_cache(cfg, batch: int, max_seq: int, abstract: bool):
    m = cfg.mla
    dt = jnp.dtype(cfg.dtype)
    shapes = {"c_kv": (batch, max_seq, m.kv_lora_rank),
              "k_rope": (batch, max_seq, m.qk_rope_dim)}
    if abstract:
        return {k: jax.ShapeDtypeStruct(v, dt) for k, v in shapes.items()}
    return {k: jnp.zeros(v, dt) for k, v in shapes.items()}


def mla_cache_axes():
    return {"c_kv": ("batch", "kv_seq", "kv_lora"),
            "k_rope": ("batch", "kv_seq", None)}


def mla_decode(cfg, p, x, cur_pos, cache, prefix: str = ""):
    """Absorbed-form single-token decode over the compressed cache."""
    pre = prefix + "/" if prefix else ""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    positions = jnp.full((1,), cur_pos, dtype=jnp.int32)
    q_nope, q_rope = _project_q(cfg, p, x, positions, pre)   # (b,1,h,*)
    c_new, r_new = _project_kv_latent(cfg, p, x, positions, pre)
    cache = {
        "c_kv": jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_new.astype(cache["c_kv"].dtype), cur_pos, axis=1),
        "k_rope": jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], r_new.astype(cache["k_rope"].dtype), cur_pos,
            axis=1),
    }
    wkv_b = p[f"{pre}wkv_b"].astype(x.dtype).reshape(
        m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
    w_uk = wkv_b[..., :m.qk_nope_dim]            # (kv_lora, h, nope)
    w_uv = wkv_b[..., m.qk_nope_dim:]            # (kv_lora, h, v)
    # absorb W_UK into the query: q_c (b,1,h,kv_lora)
    q_c = jnp.einsum("bthn,khn->bthk", q_nope, w_uk)
    scale = _softmax_scale(cfg)
    s = cache["c_kv"].shape[1]
    kv_pos = jnp.arange(s)
    mask = kv_pos <= cur_pos                     # (s,)
    scores = (jnp.einsum("bthk,bsk->bhts", q_c.astype(jnp.float32),
                         cache["c_kv"].astype(jnp.float32))
              + jnp.einsum("bthr,bsr->bhts", q_rope.astype(jnp.float32),
                           cache["k_rope"].astype(jnp.float32))) * scale
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_c = jnp.einsum("bhts,bsk->bthk", probs,
                       cache["c_kv"].astype(jnp.float32))   # (b,1,h,kv_lora)
    out = jnp.einsum("bthk,khv->bthv", out_c.astype(x.dtype), w_uv)
    out = out.reshape(b, 1, -1) @ p[f"{pre}wo"].astype(x.dtype)
    return out, cache
