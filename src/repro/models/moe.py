"""Mixture-of-Experts layer: top-k router + grouped matmul experts.

Two implementations:
  * 'ragged' — sort tokens by expert and run ``jax.lax.ragged_dot`` grouped
    matmuls (MegaBlocks-style; FLOPs scale with *active* experts only).
  * 'dense'  — capacity-based one-hot dispatch/combine einsums (GShard-style
    fallback; used if ragged_dot will not partition on some topology).

Experts are tensor-parallel on the expert-FFN dimension ('expert_mlp' →
'model' mesh axis) by default; an expert-parallel variant ('experts' →
'model', tokens all-to-all) is a §Perf hillclimb option in the launcher.
A layer may hold only a share of the experts, ``MoEConfig.n_held`` of them
from ``held_offset``, as one chip of an expert-parallel group does: the
router scores all experts and the layer computes its held experts' part of
the result for the tokens routed to them ('ragged' only). Under DeepSeek-V2's
device-level budget (``MoEConfig.device_capacity``, arXiv:2405.04434
§2.2.4) the held experts keep, per sequence, the pairs of highest routing
weight up to the budget and always compute the budget's rows: in a
synchronous expert-parallel silo every chip waits for the fullest, which
the budget holds at its share.
Shared experts (DeepSeek/Llama4) are plain dense MLPs added to the output.
The router aux load-balance loss is returned to the caller and added to each
client's local objective.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_mlp, init_mlp
from repro.utils.spans import MOE


_MOE_MESH = None  # set by the launcher for the 'ragged_shmap' impl


def set_moe_mesh(mesh):
    """Launcher hook: mesh used by the shard_map MoE implementation."""
    global _MOE_MESH
    _MOE_MESH = mesh


def init_moe(ctx, cfg):
    m = cfg.moe
    d = cfg.d_model
    ctx.param("router", (d, m.n_experts), ("embed", "experts"), scale=0.02)
    ctx.param("w_gate", (m.held, d, m.d_ff_expert),
              ("experts", "embed", "expert_mlp"))
    ctx.param("w_up", (m.held, d, m.d_ff_expert),
              ("experts", "embed", "expert_mlp"))
    ctx.param("w_down", (m.held, m.d_ff_expert, d),
              ("experts", "expert_mlp", "embed"))
    if m.n_shared:
        ff = m.d_ff_shared or m.d_ff_expert * m.n_shared
        init_mlp(ctx.sub("shared"), d, ff)


def _router(cfg, p, x, pre, rows: int):
    """x: (T, d), ``rows`` sequences of T / rows tokens -> (weights (T, k)
    float32, idx (T, k), aux_loss). Scores all n_experts, held or not."""
    m = cfg.moe
    E, k = m.n_experts, m.top_k
    w = p[f"{pre}router"]
    if m.router_f32:
        logits = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
    else:
        logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, k)
    if m.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    one_hot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
    if m.seq_aux:
        # DeepSeek-V2, per sequence: sum_e f_e P_e, f_e = E / (t k) times
        # the picks of e, P_e its mean score; averaged over the sequences
        t = x.shape[0] // rows
        count = jnp.sum(one_hot, axis=1).reshape(rows, t, E).sum(1)
        score = jnp.mean(probs.reshape(rows, t, E), axis=1)
        aux = (jnp.mean(jnp.sum(count * (E / (t * k)) * score, -1))
               * m.router_aux_coef)
    else:
        # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
        density = jnp.mean(probs, axis=0)                   # (E,)
        frac = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)   # (E,)
        aux = E * jnp.sum(frac * density) * m.router_aux_coef
    return weights, idx, aux


def _dispatch(m, weights, idx, rows: int):
    """The buffer of the held experts' grouped matmuls: per sequence of t
    tokens, room for the t * min(k, held) pairs it can send here. The pairs
    routed to a held expert come first, by falling routing weight under a
    device budget, else in order, and are kept up to ``m.budget(t)``; the
    rest is padding. Returns (pair of each row, whether it holds a kept
    one, expert group sizes, pairs routed here), the rows sorted by expert,
    padding last. Under a device budget the last group takes padding rows
    up to the budget, so that the grouped matmuls compute the whole budget
    whatever the routing; the rows past the groups are skipped."""
    T, k = idx.shape
    t, held = T // rows, m.held
    local = (idx - m.held_offset).reshape(rows, t * k)
    mine = (local >= 0) & (local < held)
    key = (jnp.where(mine, -weights.reshape(rows, t * k), jnp.inf)
           if m.device_capacity else ~mine)
    cap = m.budget(t)
    pick = jnp.argsort(key, axis=-1)[:, :t * min(k, held)]  # stable
    kept = (jnp.take_along_axis(mine, pick, -1)
            & (jnp.arange(pick.shape[1]) < cap)).reshape(-1)
    slot = jnp.where(kept, jnp.take_along_axis(local, pick, -1).reshape(-1),
                     held)
    order = jnp.argsort(slot)
    pair = (pick + t * k * jnp.arange(rows)[:, None]).reshape(-1)[order]
    sizes = jnp.bincount(slot, length=held + 1)[:held].astype(jnp.int32)
    if m.device_capacity:
        sizes = sizes.at[held - 1].add(rows * cap - jnp.sum(sizes))
    return pair, kept[order], sizes, jnp.sum(mine)


def _moe_ragged(cfg, p, x, weights, idx, pre, rows: int = 1):
    """The held experts' part of the layer, for the pairs ``_dispatch``
    keeps. Returns (out, pairs routed here, pairs kept, rows the grouped
    matmuls compute)."""
    m = cfg.moe
    T, d = x.shape
    k = m.top_k
    pair, kept, group_sizes, routed = _dispatch(m, weights, idx, rows)
    n = pair.shape[0]
    # padding rows are zeroed, so that nothing flows back from them into x
    xs = jnp.where(kept[:, None], x[pair // k], 0)           # (n, d)
    h = (jax.nn.silu(jax.lax.ragged_dot(xs, p[f"{pre}w_gate"].astype(x.dtype),
                                        group_sizes))
         * jax.lax.ragged_dot(xs, p[f"{pre}w_up"].astype(x.dtype),
                              group_sizes))
    y = jax.lax.ragged_dot(h, p[f"{pre}w_down"].astype(x.dtype), group_sizes)
    # each pair's row of y, or the zero row n for the pairs not kept
    row = jnp.full((T * k,), n, jnp.int32).at[
        jnp.where(kept, pair, T * k)].set(jnp.arange(n), mode="drop")
    y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)])[row]
    out = jnp.sum(y.reshape(T, k, d) * weights.astype(x.dtype)[..., None],
                  axis=1)
    return out, routed, jnp.sum(kept), jnp.sum(group_sizes)


def _moe_dense(cfg, p, x, weights, idx, pre):
    """Capacity-based dispatch/combine (GShard). Exact when capacity covers
    all routed tokens; tokens over capacity are dropped (standard)."""
    m = cfg.moe
    T, d = x.shape
    cap = max(1, int(m.capacity_factor * T * m.top_k / m.n_experts))
    one_hot = jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32)  # (T,k,E)
    pos = jnp.cumsum(one_hot, axis=0) * one_hot - 1.0              # slot ids
    keep = (pos < cap) & (one_hot > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    dispatch = jnp.einsum("tke,tkec->tec", one_hot * keep, pos_oh)
    combine = jnp.einsum("tk,tke,tkec->tec", weights.astype(jnp.float32),
                         one_hot * keep, pos_oh)
    xe = jnp.einsum("td,tec->ecd", x.astype(jnp.float32), dispatch)
    xe = xe.astype(x.dtype)
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p[f"{pre}w_gate"]
                                .astype(x.dtype)))
         * jnp.einsum("ecd,edf->ecf", xe, p[f"{pre}w_up"].astype(x.dtype)))
    y = jnp.einsum("ecf,efd->ecd", h, p[f"{pre}w_down"].astype(x.dtype))
    out = jnp.einsum("ecd,tec->td", y.astype(jnp.float32), combine)
    return out.astype(x.dtype)


def _moe_ragged_shmap(cfg, p, x, weights, idx, pre):
    """§Perf: the ragged grouped-matmul under shard_map.

    GSPMD has no native partitioning for lax.ragged_dot and falls back to a
    dense-masked matmul that materializes a (T·k, E·d) operand — 20+ TB per
    layer for deepseek-v2 at prefill_32k. Under shard_map every device runs
    the LOCAL ragged_dot on its token shard (full experts, 1/16 of the
    expert-FFN dim) and the only collective left is the algorithmically
    required psum of the down-projection partial sums over 'model'."""
    from jax.sharding import PartitionSpec as P
    mesh = _MOE_MESH
    assert mesh is not None, "set_moe_mesh(mesh) before using ragged_shmap"

    def local(xl, wl, il, wg, wu, wd):
        yl, *_ = _moe_ragged(cfg, {f"{pre}w_gate": wg, f"{pre}w_up": wu,
                                     f"{pre}w_down": wd}, xl, wl, il, pre)
        return jax.lax.psum(yl, "model")

    tok_spec = P("data", None) if mesh.shape.get("data", 1) > 1 else P()
    from repro.utils.compat import shard_map
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec, P("data", None) if tok_spec != P() else P(),
                  P("data", None) if tok_spec != P() else P(),
                  P(None, None, "model"), P(None, None, "model"),
                  P(None, "model", None)),
        out_specs=tok_spec, check_vma=False)
    return fn(x, weights, idx.astype(jnp.int32),
              p[f"{pre}w_gate"].astype(x.dtype),
              p[f"{pre}w_up"].astype(x.dtype),
              p[f"{pre}w_down"].astype(x.dtype))


# lm_loss's metrics that count the MoE layers' rows
MOE_COUNTERS = ("moe_routed_rows", "moe_kept_rows", "moe_buffer_rows")


def no_aux():
    """The layer's extras, summed over layers: the balance loss, and the
    pairs routed to held experts and kept of them against the rows the
    grouped matmuls compute."""
    z = jnp.zeros((), jnp.float32)
    return {"balance": z, "routed_rows": z, "kept_rows": z, "buffer_rows": z}


def apply_moe(cfg, p, x, prefix: str = ""):
    """x: (b, t, d) -> (out, extras as ``no_aux`` gives them)."""
    with jax.named_scope(MOE):
        return _apply_moe(cfg, p, x, prefix)


def _apply_moe(cfg, p, x, prefix):
    pre = prefix + "/" if prefix else ""
    m = cfg.moe
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    weights, idx, balance = _router(cfg, p, xf, pre, b)
    routed = kept = buffer = b * t * m.top_k
    if m.impl == "ragged":
        out, routed, kept, buffer = _moe_ragged(cfg, p, xf, weights, idx,
                                                pre, rows=b)
    elif m.held != m.n_experts or m.device_capacity:
        raise NotImplementedError(f"impl {m.impl!r} holds all experts and "
                                  f"keeps no device budget")
    elif m.impl == "ragged_shmap":
        out = _moe_ragged_shmap(cfg, p, xf, weights.astype(x.dtype), idx, pre)
    else:
        out = _moe_dense(cfg, p, xf, weights.astype(x.dtype), idx, pre)
    if m.n_shared:
        out = out + apply_mlp(p, xf, prefix=(prefix + "/shared" if prefix
                                             else "shared"))
    aux = {"balance": balance,
           "routed_rows": jnp.asarray(routed, jnp.float32),
           "kept_rows": jnp.asarray(kept, jnp.float32),
           "buffer_rows": jnp.asarray(buffer, jnp.float32)}
    return out.reshape(b, t, d), aux
