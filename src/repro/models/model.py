"""Model assembly: layer blocks, scan-over-periods bodies, LM / enc-dec
forward passes (train, prefill, decode) and the LM loss.

Params and caches are FLAT dicts keyed by '/'-joined paths:
  embed/tok, lm_head/w, final_norm/scale,
  pre/{i}/<layer params>                      (unstacked prefix layers)
  body/{j}/<layer params>                     (leading 'layers' axis, scanned)
  enc/body/0/<layer params>                   (encoder stack, enc-dec models)
Caches mirror the layer paths.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import KIND_MAMBA, LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.layers import (apply_mlp, embed_tokens, init_embed,
                                 init_mlp, lm_logits, rms_norm)
from repro.models.params import Ctx, subtree

Constrain = Optional[Callable[[jax.Array], jax.Array]]


# ---------------------------------------------------------------------------
# per-layer init / axes
# ---------------------------------------------------------------------------

def _init_norm(ctx, cfg, name):
    if not cfg.nonparametric_ln:
        ctx.param(f"{name}/scale", (cfg.d_model,), (None,), init="zeros")


def _norm(cfg, p, name, x):
    w = None if cfg.nonparametric_ln else p[f"{name}/scale"]
    return rms_norm(x, w)


def init_layer(ctx, cfg: ModelConfig, spec: LayerSpec, cross: bool = False):
    _init_norm(ctx, cfg, "ln_seq")
    if spec.kind == KIND_MAMBA:
        mam.init_mamba(ctx.sub("mamba"), cfg)
    elif spec.attn == "mla":
        mla_mod.init_mla(ctx.sub("mla"), cfg)
    else:
        attn.init_attention(ctx.sub("attn"), cfg)
    if cross:
        _init_norm(ctx, cfg, "ln_cross")
        attn.init_attention(ctx.sub("cross"), cfg)
    if spec.mlp == "dense":
        _init_norm(ctx, cfg, "ln_mlp")
        init_mlp(ctx.sub("mlp"), cfg.d_model, cfg.d_ff)
    elif spec.mlp == "moe":
        _init_norm(ctx, cfg, "ln_mlp")
        moe_mod.init_moe(ctx.sub("moe"), cfg)


def _cross_attend(cfg, p, x, enc_k, enc_v):
    """Cross attention over precomputed encoder K/V (non-causal)."""
    b, t, _ = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    q = (x @ p["cross/wq"].astype(x.dtype)).reshape(b, t, h, dh)
    mask = jnp.ones((t, enc_k.shape[1]), dtype=bool)
    out = attn.sdpa(q, enc_k, enc_v, mask, 1.0 / np.sqrt(dh), 0.0)
    return out.reshape(b, t, -1) @ p["cross/wo"].astype(x.dtype)


def _cross_kv(cfg, p, enc_out):
    b, s, _ = enc_out.shape
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ p["cross/wk"].astype(enc_out.dtype)).reshape(b, s, kv, dh)
    v = (enc_out @ p["cross/wv"].astype(enc_out.dtype)).reshape(b, s, kv, dh)
    return k, v


def apply_layer_prefill(cfg, spec, p, x, positions, cache=None,
                        write_pos=0, enc_out=None, constrain: Constrain = None):
    """Returns (x, new_cache, aux): ``aux`` as ``moe.no_aux`` gives it."""
    aux = moe_mod.no_aux()
    new_cache: Dict[str, jax.Array] = {}
    h = _norm(cfg, p, "ln_seq", x)
    if spec.kind == KIND_MAMBA:
        lc = ({"conv": cache["mamba/conv"], "ssm": cache["mamba/ssm"]}
              if cache is not None else None)
        y, c = mam.mamba_prefill(cfg, p, h, prefix="mamba", cache=lc)
        if c is not None:
            new_cache["mamba/conv"], new_cache["mamba/ssm"] = c["conv"], c["ssm"]
    elif spec.attn == "mla":
        lc = ({"c_kv": cache["mla/c_kv"], "k_rope": cache["mla/k_rope"]}
              if cache is not None else None)
        y, c = mla_mod.mla_prefill(cfg, p, h, positions, prefix="mla",
                                   cache=lc, write_pos=write_pos)
        if c is not None:
            new_cache["mla/c_kv"], new_cache["mla/k_rope"] = c["c_kv"], c["k_rope"]
    else:
        lc = ({"k": cache["attn/k"], "v": cache["attn/v"]}
              if cache is not None else None)
        y, c = attn.attn_block_prefill(cfg, spec, p, h, positions,
                                       prefix="attn", cache=lc,
                                       write_pos=write_pos)
        if c is not None:
            new_cache["attn/k"], new_cache["attn/v"] = c["k"], c["v"]
    x = x + y
    if constrain:
        x = constrain(x)
    if enc_out is not None:
        ek, ev = _cross_kv(cfg, p, enc_out)
        x = x + _cross_attend(cfg, p, _norm(cfg, p, "ln_cross", x), ek, ev)
        if cache is not None:
            new_cache["cross/k"], new_cache["cross/v"] = ek, ev
    if spec.mlp == "dense":
        x = x + apply_mlp(p, _norm(cfg, p, "ln_mlp", x), prefix="mlp")
    elif spec.mlp == "moe":
        y, aux = moe_mod.apply_moe(cfg, p, _norm(cfg, p, "ln_mlp", x),
                                   prefix="moe")
        x = x + y
    if constrain:
        x = constrain(x)
    return x, new_cache, aux


def apply_layer_decode(cfg, spec, p, x, cur_pos, cache):
    """Single-token decode. Returns (x, new_cache)."""
    new_cache = dict(cache)
    h = _norm(cfg, p, "ln_seq", x)
    if spec.kind == KIND_MAMBA:
        y, c = mam.mamba_decode(
            cfg, p, h, {"conv": cache["mamba/conv"], "ssm": cache["mamba/ssm"]},
            prefix="mamba")
        new_cache["mamba/conv"], new_cache["mamba/ssm"] = c["conv"], c["ssm"]
    elif spec.attn == "mla":
        y, c = mla_mod.mla_decode(
            cfg, p, h, cur_pos,
            {"c_kv": cache["mla/c_kv"], "k_rope": cache["mla/k_rope"]},
            prefix="mla")
        new_cache["mla/c_kv"], new_cache["mla/k_rope"] = c["c_kv"], c["k_rope"]
    else:
        y, c = attn.attn_block_decode(
            cfg, spec, p, h, cur_pos,
            {"k": cache["attn/k"], "v": cache["attn/v"]}, prefix="attn")
        new_cache["attn/k"], new_cache["attn/v"] = c["k"], c["v"]
    x = x + y
    if "cross/k" in cache:
        x = x + _cross_attend(cfg, p, _norm(cfg, p, "ln_cross", x),
                              cache["cross/k"], cache["cross/v"])
    if spec.mlp == "dense":
        x = x + apply_mlp(p, _norm(cfg, p, "ln_mlp", x), prefix="mlp")
    elif spec.mlp == "moe":
        y, _ = moe_mod.apply_moe(cfg, p, _norm(cfg, p, "ln_mlp", x),
                                 prefix="moe")
        x = x + y
    return x, new_cache


# ---------------------------------------------------------------------------
# whole-model init
# ---------------------------------------------------------------------------

def build_params(cfg: ModelConfig, key=None, abstract: bool = False):
    """Returns (params flat dict, axes flat dict)."""
    ctx = Ctx(key, cfg.param_dtype, abstract=abstract)
    root = ctx.sub("")
    init_embed(root, cfg)
    if cfg.encdec:
        enc_spec = LayerSpec()  # full-attn dense encoder layer
        init_layer(root.stacked("enc/body/0", cfg.n_enc_layers), cfg, enc_spec)
        _init_norm(root.sub("enc"), cfg, "final_norm")
    for i, spec in enumerate(cfg.prefix):
        init_layer(root.sub(f"pre/{i}"), cfg, spec, cross=cfg.encdec)
    for j, spec in enumerate(cfg.schedule):
        init_layer(root.stacked(f"body/{j}", cfg.n_periods), cfg, spec,
                   cross=cfg.encdec)
    _init_norm(root, cfg, "final_norm")
    return ctx.params, ctx.axes


def init_lm(cfg: ModelConfig, key):
    return build_params(cfg, key=key, abstract=False)


def abstract_lm(cfg: ModelConfig):
    return build_params(cfg, key=None, abstract=True)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _layer_cache(cfg, spec, batch, max_seq, abstract, cross: bool,
                 enc_len: int):
    c: Dict[str, jax.Array] = {}
    if spec.kind == KIND_MAMBA:
        for k, v in mam.init_mamba_cache(cfg, batch, abstract).items():
            c[f"mamba/{k}"] = v
    elif spec.attn == "mla":
        for k, v in mla_mod.init_mla_cache(cfg, batch, max_seq,
                                           abstract).items():
            c[f"mla/{k}"] = v
    else:
        for k, v in attn.init_attn_cache(cfg, spec, batch, max_seq,
                                         abstract).items():
            c[f"attn/{k}"] = v
    if cross:
        shape = (batch, enc_len, cfg.n_kv_heads, cfg.head_dim)
        dt = jnp.dtype(cfg.dtype)
        for k in ("cross/k", "cross/v"):
            c[k] = (jax.ShapeDtypeStruct(shape, dt) if abstract
                    else jnp.zeros(shape, dt))
    return c


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               abstract: bool = False, enc_len: int = 0):
    """Flat cache dict mirroring layer paths. Stacked for the body."""
    cache: Dict[str, jax.Array] = {}
    for i, spec in enumerate(cfg.prefix):
        for k, v in _layer_cache(cfg, spec, batch, max_seq, abstract,
                                 cfg.encdec, enc_len).items():
            cache[f"pre/{i}/{k}"] = v
    n = cfg.n_periods
    for j, spec in enumerate(cfg.schedule):
        for k, v in _layer_cache(cfg, spec, batch, max_seq, abstract,
                                 cfg.encdec, enc_len).items():
            shape = (n,) + tuple(v.shape)
            cache[f"body/{j}/{k}"] = (
                jax.ShapeDtypeStruct(shape, v.dtype) if abstract
                else jnp.zeros(shape, v.dtype))
    return cache


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _encode(cfg, params, frontend, constrain: Constrain = None):
    """Bidirectional encoder over stub frontend embeddings (b, t, d)."""
    x = frontend.astype(jnp.dtype(cfg.dtype))
    body = subtree(params, "enc/body/0")
    positions = jnp.arange(x.shape[1])

    def step(carry, p_slice):
        h = _norm(cfg, p_slice, "ln_seq", carry)
        b, t, _ = h.shape
        hh, dh = cfg.n_heads, cfg.head_dim
        q = (h @ p_slice["attn/wq"].astype(h.dtype)).reshape(b, t, hh, dh)
        k = (h @ p_slice["attn/wk"].astype(h.dtype)).reshape(
            b, t, cfg.n_kv_heads, dh)
        v = (h @ p_slice["attn/wv"].astype(h.dtype)).reshape(
            b, t, cfg.n_kv_heads, dh)
        from repro.models.layers import apply_rope
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        mask = jnp.ones((t, t), dtype=bool)
        o = attn.sdpa(q, k, v, mask, 1.0 / np.sqrt(dh), cfg.attn_softcap)
        y = o.reshape(b, t, -1) @ p_slice["attn/wo"].astype(h.dtype)
        out = carry + y
        out = out + apply_mlp(p_slice, _norm(cfg, p_slice, "ln_mlp", out),
                              prefix="mlp")
        if constrain:
            out = constrain(out)
        return out, None

    x, _ = jax.lax.scan(step, x, body)
    return _norm(cfg, subtree(params, "enc"), "final_norm", x)


def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def forward(cfg: ModelConfig, params, batch, *, cache=None, write_pos=0,
            remat: bool = False, constrain: Constrain = None):
    """Full-sequence forward (train / prefill).

    batch: {'tokens': (b, t_text)} plus 'frontend': (b, t_f, d) for vlm/audio.
    Returns (logits over text positions, new_cache, aux), ``aux`` the MoE
    layers' extras summed (``moe.no_aux``).
    """
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    enc_out = None
    if cfg.encdec:
        enc_out = _encode(cfg, params, batch["frontend"], constrain)
    elif cfg.frontend:
        fe = batch["frontend"].astype(x.dtype)
        x = jnp.concatenate([fe, x], axis=1)     # early fusion: prepend
    if constrain:
        x = constrain(x)
    b, t, _ = x.shape
    positions = jnp.arange(t)
    aux = moe_mod.no_aux()
    new_cache: Dict[str, jax.Array] = {}

    for i, spec in enumerate(cfg.prefix):
        lc = subtree(cache, f"pre/{i}") if cache is not None else None

        def layer(p, x, lc, spec=spec):
            return apply_layer_prefill(cfg, spec, p, x, positions, cache=lc,
                                       write_pos=write_pos, enc_out=enc_out,
                                       constrain=constrain)

        layer = jax.checkpoint(layer) if remat else layer
        x, c, a = layer(subtree(params, f"pre/{i}"), x, lc)
        aux = _add(aux, a)
        for k, v in c.items():
            new_cache[f"pre/{i}/{k}"] = v

    body_p = {j: subtree(params, f"body/{j}")
              for j in range(len(cfg.schedule))}
    body_c = ({j: subtree(cache, f"body/{j}")
               for j in range(len(cfg.schedule))} if cache is not None
              else None)

    def period(carry, xs):
        x, aux = carry
        p_sl = xs["p"]
        c_sl = xs.get("c")
        outs = {}
        for j, spec in enumerate(cfg.schedule):
            lc = c_sl[j] if c_sl is not None else None
            x, c, a = apply_layer_prefill(cfg, spec, p_sl[j], x, positions,
                                          cache=lc, write_pos=write_pos,
                                          enc_out=enc_out,
                                          constrain=constrain)
            aux = _add(aux, a)
            if c:
                outs[j] = c
        return (x, aux), outs

    step_fn = jax.checkpoint(period) if remat else period
    xs = {"p": body_p}
    if body_c is not None:
        xs["c"] = body_c
    (x, aux), body_new = jax.lax.scan(step_fn, (x, aux), xs)
    if cache is not None:
        for j, sub in body_new.items():
            for k, v in sub.items():
                new_cache[f"body/{j}/{k}"] = v

    x = _norm(cfg, params, "final_norm", x)
    if cfg.frontend and not cfg.encdec:
        x = x[:, -tokens.shape[1]:]              # logits over text positions
    logits = lm_logits(cfg, params, x)
    return logits, (new_cache if cache is not None else None), aux


def decode_step(cfg: ModelConfig, params, token, cur_pos, cache):
    """One-token decode. token: (b, 1) int32; cur_pos: scalar int32 (absolute
    position of this token, i.e. tokens already in cache). Returns
    (logits (b, 1, V), new_cache)."""
    x = embed_tokens(cfg, params, token)
    new_cache: Dict[str, jax.Array] = {}
    for i, spec in enumerate(cfg.prefix):
        x, c = apply_layer_decode(cfg, spec, subtree(params, f"pre/{i}"), x,
                                  cur_pos, subtree(cache, f"pre/{i}"))
        for k, v in c.items():
            new_cache[f"pre/{i}/{k}"] = v

    body_p = {j: subtree(params, f"body/{j}")
              for j in range(len(cfg.schedule))}
    body_c = {j: subtree(cache, f"body/{j}")
              for j in range(len(cfg.schedule))}

    def period(x, xs):
        outs = {}
        for j, spec in enumerate(cfg.schedule):
            x, c = apply_layer_decode(cfg, spec, xs["p"][j], x, cur_pos,
                                      xs["c"][j])
            outs[j] = c
        return x, outs

    x, body_new = jax.lax.scan(period, x, {"p": body_p, "c": body_c})
    for j, sub in body_new.items():
        for k, v in sub.items():
            new_cache[f"body/{j}/{k}"] = v
    x = _norm(cfg, params, "final_norm", x)
    return lm_logits(cfg, params, x), new_cache


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def lm_loss(cfg: ModelConfig, params, batch, *, remat: bool = False,
            constrain: Constrain = None):
    """Next-token cross-entropy (+ MoE balance loss). Returns (loss,
    metrics); the metrics count the MoE layers' routed, kept and buffer
    rows."""
    logits, _, aux = forward(cfg, params, batch, remat=remat,
                             constrain=constrain)
    tokens = batch["tokens"]
    targets = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    ce = jnp.sum((lse - tgt) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = ce + aux["balance"]
    return loss, {"ce": ce, "aux": aux["balance"],
                  "moe_routed_rows": aux["routed_rows"],
                  "moe_kept_rows": aux["kept_rows"],
                  "moe_buffer_rows": aux["buffer_rows"]}
