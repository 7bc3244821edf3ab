"""Plain float32 reference of DeepSeek-V2-Lite as the benchmark runs it: one
chip's share of an 8-chip expert-parallel silo (the held experts, the
vocabulary slice), and the weights both sides start from.

The weights are made from the seed by :func:`weights`, in the flat layout
the system under test takes (``pre/0/mla/wq`` for the dense first layers,
``body/0/moe/w_gate`` ... with a leading layer axis for the MoE layers).
The forward pass follows DeepSeek-V2's ``modeling_deepseek.py`` with the
departures the configuration file lists:

- pre-norm blocks, RMSNorm with weight 1 + w;
- multi-head latent attention in the expanded form, with no query LoRA: q
  from one projection, the key-value latent and a head-shared rope key from
  ``wkv_a``, the latent normed and expanded by ``wkv_b`` into per-head keys
  and values; YaRN rope (written out here from DeepSeek-V2's formulas),
  the softmax scaled by mscale(mscale_all_dim) squared;
- the first ``first_k_dense_replace`` layers with a SwiGLU MLP, the rest
  with a softmax router over all routed experts, greedy top-k, weights not
  renormalised, times ``routed_scaling_factor``; of each sequence's pairs
  routed to the held experts, those past the device budget
  (``device_capacity_factor`` times the held experts' share of the picks)
  with the lowest weights are dropped; each held expert makes a dense pass
  over every token, weighted by its routing weight (0 where it was not
  chosen or was dropped), and the shared experts (one SwiGLU MLP) are
  added;
- DeepSeek-V2's sequence-wise balance loss, per layer.

Every matrix product goes through ``ein``, which the caller picks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg):
    m = {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
         "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
         "dv": cfg["v_head_dim"], "c": cfg["kv_lora_rank"],
         "ff": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
         "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
         "E": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
         "held": cfg["n_routed_experts_held"],
         "off": cfg["n_routed_experts_offset"],
         "dense": cfg["first_k_dense_replace"], "V": cfg["vocab_size"]}
    m["L"] = cfg["num_hidden_layers"] - m["dense"]
    m["qd"] = m["nope"] + m["rope"]
    return m


def _block_shapes(m, lead=()):
    d, h = m["d"], m["h"]
    return {"ln_seq/scale": lead + (d,), "ln_mlp/scale": lead + (d,),
            "mla/wq": lead + (d, h * m["qd"]),
            "mla/wkv_a": lead + (d, m["c"] + m["rope"]),
            "mla/kv_norm/scale": lead + (m["c"],),
            "mla/wkv_b": lead + (m["c"], h * (m["nope"] + m["dv"])),
            "mla/wo": lead + (h * m["dv"], d)}


def shapes(cfg):
    """{leaf: shape} of the parameter tree."""
    m = dims(cfg)
    d, L = m["d"], (m["L"],)
    out = {"embed/tok": (m["V"], d), "lm_head/w": (d, m["V"]),
           "final_norm/scale": (d,)}
    for i in range(m["dense"]):
        blk = {**_block_shapes(m), "mlp/w_gate": (d, m["ff"]),
               "mlp/w_up": (d, m["ff"]), "mlp/w_down": (m["ff"], d)}
        out.update({f"pre/{i}/{k}": s for k, s in blk.items()})
    blk = {**_block_shapes(m, L), "moe/router": L + (d, m["E"]),
           "moe/w_gate": L + (m["held"], d, m["fe"]),
           "moe/w_up": L + (m["held"], d, m["fe"]),
           "moe/w_down": L + (m["held"], m["fe"], d),
           "moe/shared/w_gate": L + (d, m["fs"]),
           "moe/shared/w_up": L + (d, m["fs"]),
           "moe/shared/w_down": L + (m["fs"], d)}
    out.update({f"body/0/{k}": s for k, s in blk.items()})
    return out


def weights(cfg, key):
    """Norm weights w = 0; matrices normal, scaled by 1/sqrt(fan in); one
    key per leaf."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        if name.endswith("/scale"):
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        fan_in = shape[-1] if name == "embed/tok" else shape[-2]
        out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32)
                     / np.float32(math.sqrt(fan_in)))
    return out


def matmul_params(cfg) -> int:
    """Weights that enter a matrix product per token: every matrix but the
    input lookup, the held routed experts at the share of a token they
    see, top_k x held / n_routed_experts experts (0.75 at 6 x 8 / 64),
    which is also the rows the device budget computes at capacity 1.0."""
    m = dims(cfg)
    n = 0
    for name, s in shapes(cfg).items():
        if len(s) < 2 or name == "embed/tok":
            continue
        size = int(np.prod(s))
        if name.startswith("body/0/moe/w_"):
            size = size * m["k"] // m["E"]
        n += size
    return n


def flops_per_token(cfg, seq: int) -> float:
    """Forward and backward: 6 per matmul weight (routed experts at the
    expected 0.75 a token), plus 6 T h (qd + dv) a layer for the attention
    scores and their weighted sum (causal mask not discounted,
    recomputation not counted)."""
    m = dims(cfg)
    layers = m["dense"] + m["L"]
    return (6.0 * matmul_params(cfg)
            + 6.0 * layers * seq * m["h"] * (m["qd"] + m["dv"]))


# -- YaRN, from DeepSeek-V2's DeepseekV2YarnRotaryEmbedding -------------------

def _get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(cfg):
    """(inverse frequencies (rope/2,) float32, cos/sin factor, softmax
    scale), in float64 before the cast."""
    rs, base, dim = cfg["rope_scaling"], float(cfg["rope_theta"]), \
        cfg["qk_rope_head_dim"]
    factor = float(rs["factor"])
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    freq_extra = 1.0 / base ** exps
    freq_inter = 1.0 / (factor * base ** exps)

    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1.0 - mask) + freq_extra * mask
    cs = (_get_mscale(factor, rs["mscale"])
          / _get_mscale(factor, rs["mscale_all_dim"]))
    qd = cfg["qk_nope_head_dim"] + dim
    softmax_scale = qd ** -0.5 * _get_mscale(factor,
                                             rs["mscale_all_dim"]) ** 2
    return inv_freq.astype(np.float32), np.float32(cs), np.float32(
        softmax_scale)


def _rope(x, inv_freq, cs):
    """x: (t, heads, dim), the two halves rotated."""
    t, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    cos, sin = (jnp.cos(ang) * cs)[:, None], (jnp.sin(ang) * cs)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rms(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + w))


def _swiglu(ein, h, g, u, dn):
    a = jax.nn.silu(ein("td,df->tf", h, g)) * ein("td,df->tf", h, u)
    return ein("tf,fd->td", a, dn)


def _mla(cfg, m, p, h, ein, rope):
    t, H = h.shape[0], m["h"]
    inv_freq, cs, scale = rope
    q = ein("td,de->te", h, p["mla/wq"]).reshape(t, H, m["qd"])
    q = jnp.concatenate([q[..., :m["nope"]],
                         _rope(q[..., m["nope"]:], inv_freq, cs)], -1)
    kv = ein("td,de->te", h, p["mla/wkv_a"])
    c = _rms(kv[:, :m["c"]], p["mla/kv_norm/scale"], cfg["rms_norm_eps"])
    k_pe = _rope(kv[:, None, m["c"]:], inv_freq, cs)          # (t, 1, rope)
    kvb = ein("tc,ce->te", c, p["mla/wkv_b"]).reshape(
        t, H, m["nope"] + m["dv"])
    k = jnp.concatenate([kvb[..., :m["nope"]],
                         jnp.broadcast_to(k_pe, (t, H, m["rope"]))], -1)
    v = kvb[..., m["nope"]:]
    s = ein("thd,shd->hts", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = ein("hts,shd->thd", jax.nn.softmax(s, -1), v).reshape(t, -1)
    return ein("te,ed->td", o, p["mla/wo"])


def budget(cfg, t):
    """The pairs a sequence of t tokens may send to the held experts:
    ``device_capacity_factor`` times their share of its t top_k picks, at
    most all the picks that can land there (0: no budget)."""
    k, held = cfg["num_experts_per_tok"], cfg["n_routed_experts_held"]
    room = t * min(k, held)
    f = cfg["device_capacity_factor"]
    return min(room, math.ceil(f * t * k * held / cfg["n_routed_experts"])
               ) if f else room


def _moe(cfg, m, p, h, ein):
    """(the held experts' part plus the shared experts, balance loss)."""
    t, E, k = h.shape[0], m["E"], m["k"]
    probs = jax.nn.softmax(ein("td,de->te", h, p["moe/router"]), -1)
    top_w, top_i = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    picks = jax.nn.one_hot(top_i, E, dtype=jnp.float32)      # (t, k, E)
    # the device budget: of the pairs routed to the held experts, the
    # budget's worth of highest weight are kept (top_k: equal weights, the
    # earlier pair), the rest dropped
    held = (top_i >= m["off"]) & (top_i < m["off"] + m["held"])
    _, best = jax.lax.top_k(jnp.where(held, top_w, -1.0).reshape(-1),
                            budget(cfg, t))
    kept = held & jnp.zeros(t * k, bool).at[best].set(True).reshape(t, k)
    top_w = jnp.where(kept, top_w, 0.0) * cfg["routed_scaling_factor"]
    gate = jnp.sum(picks * top_w[..., None], 1)              # (t, E)
    gate = gate[:, m["off"]:m["off"] + m["held"]]            # (t, held)
    a = (jax.nn.silu(ein("td,edf->etf", h, p["moe/w_gate"]))
         * ein("td,edf->etf", h, p["moe/w_up"]))
    y = ein("etf,efd->etd", a, p["moe/w_down"])              # (held, t, d)
    out = jnp.sum(y * gate.T[..., None], 0)
    out = out + _swiglu(ein, h, p["moe/shared/w_gate"],
                        p["moe/shared/w_up"], p["moe/shared/w_down"])
    f = jnp.sum(picks, (0, 1)) * (E / (t * k))
    balance = jnp.sum(f * jnp.mean(probs, 0)) * cfg["aux_loss_alpha"]
    return out, balance


def row_loss(cfg, params, toks, ein):
    """Summed next-token cross-entropy of one (T,) row of tokens, plus the
    row's balance loss times its T - 1 predictions: the program's objective
    is the mean cross-entropy plus the balance loss averaged over rows, and
    the caller divides the summed rows by b (T - 1)."""
    m = dims(cfg)
    eps = cfg["rms_norm_eps"]
    t = toks.shape[0]
    rope = yarn(cfg)
    x = params["embed/tok"][toks]
    for i in range(m["dense"]):
        p = {k[len(f"pre/{i}/"):]: v for k, v in params.items()
             if k.startswith(f"pre/{i}/")}
        x = x + _mla(cfg, m, p, _rms(x, p["ln_seq/scale"], eps), ein, rope)
        h = _rms(x, p["ln_mlp/scale"], eps)
        x = x + _swiglu(ein, h, p["mlp/w_gate"], p["mlp/w_up"],
                        p["mlp/w_down"])

    def layer(carry, p):
        x, bal = carry
        x = x + _mla(cfg, m, p, _rms(x, p["ln_seq/scale"], eps), ein, rope)
        y, b = _moe(cfg, m, p, _rms(x, p["ln_mlp/scale"], eps), ein)
        return (x + y, bal + b), None

    body = {k[len("body/0/"):]: v for k, v in params.items()
            if k.startswith("body/0/")}
    (x, balance), _ = jax.lax.scan(jax.checkpoint(layer),
                                   (x, jnp.float32(0.0)), body)
    x = _rms(x, params["final_norm/scale"], eps)
    logits = ein("td,dv->tv", x, params["lm_head/w"])[:-1]
    tgt = jnp.take_along_axis(logits, toks[1:, None], -1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - tgt) + balance * (t - 1)
