"""Distributed step builders: QuAFL train_step, prefill_step, serve_step.

The QuAFL mapping onto the mesh (DESIGN.md §3):
  * client_dp — client replicas stacked on a leading 'clients' axis sharded
    over the mesh 'data' axis (one divergent replica per data slice, tensor
    parallel over 'model' inside).
  * cohort    — one client per POD (giant architectures): parameters are
    FSDP-sharded over data×model; on the single-pod mesh n_slots=1 and QuAFL
    runs its s=1 instance (server + one cohort, still fully quantized).

train_step executes ONE server round of Algorithm 1: every client slot runs
up to K masked local SGD steps on its own microbatch stream, both directions
of the exchange are lattice-quantized, and the (s+1)-averaging preserves the
model mean. Asynchrony: each slot draws H_i ~ min(K, Poisson(λ_i·Δt)) inside
the step (paper App. B.1 equivalence).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compression.codecs import resolve_codec
from repro.compression.transports import transport_for_mode
from repro.configs.base import FedConfig, ModelConfig, ShapeConfig
from repro.core.quafl import client_speeds
from repro.core.transport import leaf_dist, tree_decode, tree_encode
from repro.launch.specs import (abstract_cache, enc_len_for, input_axes,
                                input_specs)
from repro.models.model import (abstract_lm, decode_step, forward, init_cache,
                                lm_loss)
from repro.models.moe import MOE_COUNTERS
from repro.sharding.rules import pspec_for, rules_for_mode
from repro.utils.spans import EXCHANGE, LOCAL_STEPS

# architectures too large for per-data-slice client replicas get cohort mode
FED_MODE: Dict[str, str] = {
    "llama4-scout-17b-a16e": "cohort",
    "deepseek-v2-236b": "cohort",
    "jamba-1.5-large-398b": "cohort",
    "llava-next-34b": "cohort",
}


def fed_mode_for(arch_name: str) -> str:
    return FED_MODE.get(arch_name, "client_dp")


class TrainState(NamedTuple):
    server: Dict[str, Any]     # X_t
    clients: Dict[str, Any]    # X^i, leaves have a leading (n_slots,) axis
    t: jnp.ndarray


def n_slots_for(mesh, fed_mode: str) -> int:
    if fed_mode == "cohort":
        return int(mesh.shape.get("pod", 1))
    return int(mesh.shape["data"])


# ---------------------------------------------------------------------------
# abstract state + shardings
# ---------------------------------------------------------------------------

def abstract_train_state(cfg: ModelConfig, mesh, fed_mode: str):
    """(state spec tree, state shardings) for the dry-run."""
    spec, axes = abstract_lm(cfg)
    n = n_slots_for(mesh, fed_mode)
    rules = rules_for_mode(fed_mode)
    cl_spec = {k: jax.ShapeDtypeStruct((n,) + tuple(v.shape), v.dtype)
               for k, v in spec.items()}
    cl_axes = {k: ("clients",) + tuple(v) for k, v in axes.items()}
    srv_sh = {k: NamedSharding(mesh, pspec_for(v.shape, axes[k], rules, mesh))
              for k, v in spec.items()}
    cl_sh = {k: NamedSharding(mesh, pspec_for(cl_spec[k].shape, cl_axes[k],
                                              rules, mesh))
             for k in spec}
    state = TrainState(server=spec, clients=cl_spec,
                       t=jax.ShapeDtypeStruct((), jnp.int32))
    shardings = TrainState(server=srv_sh, clients=cl_sh,
                           t=NamedSharding(mesh, P()))
    return state, shardings


def init_train_state(cfg: ModelConfig, key, n_slots: int) -> TrainState:
    from repro.models.model import init_lm
    params, _ = init_lm(cfg, key)
    clients = {k: jnp.broadcast_to(v[None], (n_slots,) + v.shape)
               for k, v in params.items()}
    return TrainState(server=params, clients=clients,
                      t=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# train step (one QuAFL round)
# ---------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, fed: FedConfig, mesh, shape: ShapeConfig,
                     *, fed_mode: str = None, transport: str = None,
                     quantized: bool = True, remat: bool = True):
    """Returns (train_step, state_spec, in_shardings tuple)."""
    fed_mode = fed_mode or fed_mode_for(cfg.name)
    transport = transport or fed.transport
    n_slots = n_slots_for(mesh, fed_mode)
    rules = rules_for_mode(fed_mode)
    K, lr = fed.local_steps, fed.lr
    # per-direction codecs (repro.compression.codecs): lattice by default,
    # any registry codec via fed.codec_up / codec_down; `quantized=False`
    # forces the uncompressed identity pair
    spec = None if quantized else "identity"
    quant_up = resolve_codec(spec, fed, direction="up", default="lattice")
    quant_down = resolve_codec(spec, fed, direction="down",
                               default="lattice")
    # stateful codecs degrade to their stateless encode on the mesh path
    # (no per-client residual buffers in the train state)

    lam = client_speeds(fed, n_slots) if n_slots > 1 else np.array(
        [fed.lam_fast], np.float32)
    H = np.minimum(K, np.maximum(lam * (fed.swt + fed.sit), 1e-3))
    eta_i = ((H.min() / H) if fed.weighted else np.ones(n_slots)).astype(
        np.float32)

    # an MoE model counts its layers' routed, kept and buffer rows in each
    # step
    counted = MOE_COUNTERS if cfg.moe is not None else ()

    def local_round(cp, toks, fe, h_i, key):
        """One client slot: up to K masked local steps. toks: (K, b, t).
        Returns (X_K, {counter: (K,)})."""
        def loss_fn(p, batch):
            loss, m = lm_loss(cfg, p, batch, remat=remat)
            return loss, {k: m[k] for k in counted}

        def step(p, q):
            batch = {"tokens": toks[q]}
            if fe is not None:
                batch["frontend"] = fe[q]
            g, rows = jax.grad(loss_fn, has_aux=True)(p, batch)
            act = (q < h_i).astype(jnp.float32)
            p = {k: (p[k] - lr * act * g[k].astype(p[k].dtype)) for k in p}
            return p, rows

        with jax.named_scope(LOCAL_STEPS):
            pK, rows = jax.lax.scan(step, cp, jnp.arange(K))
        # Y = X - η·η_i·h̃ = (1-η_i)·X + η_i·X_K   (h̃ = (X - X_K)/η)
        return pK, rows

    # vmap over client slots keeps the HLO one-body-sized; the MoE archs run
    # in cohort mode (n_slots ∈ {1, 2}) and use an unrolled loop instead, so
    # lax.ragged_dot never needs a batching rule.
    unroll_slots = (n_slots <= 2) or (cfg.moe is not None)
    # Pin the vmapped client axis to the mesh 'data' axis INSIDE the grad
    # scan too — without this GSPMD replicates per-client grads on every
    # device (§Perf iteration 2: dominant memory+collective term).
    spmd_axis = "data" if (fed_mode == "client_dp" and
                           mesh.shape.get("data", 1) > 1) else None

    def vmap_slots(fn, in_axes=0):
        return jax.vmap(fn, in_axes=in_axes, spmd_axis_name=spmd_axis)

    def slot_progress(cp_i, toks_i, fe_i, h_i, eta, key_i):
        pK, rows = local_round(cp_i, toks_i, fe_i, h_i, key_i)
        # Y = X − η·η_i·h̃ = (1−η_i)·X + η_i·X_K
        with jax.named_scope(EXCHANGE):
            Y_i = {k: ((1.0 - eta) * cp_i[k].astype(jnp.float32)
                       + eta * pK[k].astype(jnp.float32)).astype(
                           cp_i[k].dtype) for k in cp_i}
            return Y_i, leaf_dist(Y_i, cp_i), rows

    def slot_encode(Y_i, hints_i, key_i):
        return tree_encode(quant_up, key_i, Y_i, hints_i)

    def slot_decode_up(msgs_i, key_i, server):
        return tree_decode(quant_up, key_i, msgs_i, server)

    def slot_update(cp_i, Y_i, k_srv, msg_srv, denom):
        QX_i = tree_decode(quant_down, k_srv, msg_srv, cp_i)
        return {k: (QX_i[k].astype(jnp.float32) / denom
                    + (denom - 1) * Y_i[k].astype(jnp.float32) / denom
                    ).astype(cp_i[k].dtype) for k in cp_i}

    def train_step(state: TrainState, batch, key_raw):
        key = jax.random.wrap_key_data(key_raw)
        k_h, k_q, k_loc = jax.random.split(key, 3)
        toks = batch["tokens"]                   # (n_slots, K, b, t)
        fe = batch.get("frontend")
        with jax.named_scope(LOCAL_STEPS):
            h_steps = jnp.minimum(jax.random.poisson(
                k_h, jnp.asarray(lam) * (fed.swt + fed.sit), (n_slots,)),
                K).astype(jnp.int32)
        etas = jnp.asarray(eta_i)
        loc_keys = jax.random.split(k_loc, n_slots)
        q_keys = jax.random.split(jax.random.fold_in(k_q, 1), n_slots)
        denom = n_slots + 1

        def sl(tree, i):
            return {k: v[i] for k, v in tree.items()}

        if unroll_slots:
            pieces = [slot_progress(sl(state.clients, i), toks[i],
                                    fe[i] if fe is not None else None,
                                    h_steps[i], etas[i], loc_keys[i])
                      for i in range(n_slots)]
            Ys = {k: jnp.stack([p[0][k] for p in pieces], 0)
                  for k in state.server}
            hints_up = {k: jnp.stack([p[1][k] for p in pieces], 0)
                        for k in state.server}
            rows = {k: jnp.stack([p[2][k] for p in pieces], 0)
                    for k in counted}
        else:
            Ys, hints_up, rows = vmap_slots(
                lambda cp, tk, f, h, e, kk: slot_progress(cp, tk, f, h, e, kk)
            )(state.clients, toks, fe, h_steps, etas, loc_keys) \
                if fe is not None else vmap_slots(
                lambda cp, tk, h, e, kk: slot_progress(cp, tk, None, h, e, kk)
            )(state.clients, toks, h_steps, etas, loc_keys)

        # the counters of each local step, summed over the slots
        counters = {k: jnp.sum(v, 0) for k, v in rows.items()}

        # ---- shard-local exchange (§Perf): whole exchange in shard_map ----
        if transport in ("shard_local", "shard_local_codes",
                         "shard_local_rs") and quantized:
            from repro.core.exchange_local import make_shardlocal_exchange
            rules_ = rules_for_mode(fed_mode)
            spec_, axes_ = abstract_lm(cfg)
            srv_ps = {k: pspec_for(v.shape, axes_[k], rules_, mesh)
                      for k, v in spec_.items()}
            cl_ps = {k: pspec_for((n_slots,) + tuple(v.shape),
                                  ("clients",) + tuple(axes_[k]), rules_,
                                  mesh) for k, v in spec_.items()}
            client_axis = "pod" if fed_mode == "cohort" else "data"
            ex = make_shardlocal_exchange(
                quant_up, quant_down, mesh, srv_ps, cl_ps, client_axis,
                n_slots, transport=transport_for_mode(transport))
            ex_key = jax.random.key_data(jax.random.fold_in(k_q, 3))
            # compile the local training apart from the exchange. Without
            # the barrier XLA fuses the client models Ys into whatever the
            # exchange does with them, so the kernel backend changed the
            # client models themselves: on four v5e chips the pallas and
            # jnp rounds' Ys differed by up to 1.8e-5 in the embedding and
            # their servers by up to 4.9e-5, while the exchange alone, fed
            # the same Ys, agreed to 0.0
            Ys = jax.lax.optimization_barrier(Ys)
            with jax.named_scope(EXCHANGE):
                server_new, clients_new, qerr = ex(
                    state.server, state.clients, Ys, ex_key)
            new_state = TrainState(server=server_new, clients=clients_new,
                                   t=state.t + 1)
            return new_state, {
                "h_steps_mean": jnp.mean(h_steps.astype(jnp.float32)),
                "quant_err_sq": qerr, **counters}

        with jax.named_scope(EXCHANGE):
            # ---- client -> server: Enc(Y^i), decoded against X_t ---------
            msgs_up = vmap_slots(slot_encode)(Ys, hints_up, q_keys)
            if transport == "code_allgather" and quantized:
                repl = NamedSharding(mesh, P())
                # replicate every message leaf (codes, scales, indices,
                # ...) so any codec's wire format rides this transport
                msgs_up = {k: jax.tree_util.tree_map(
                    lambda a: jax.lax.with_sharding_constraint(a, repl), m)
                    for k, m in msgs_up.items()}
            up_axis = None if transport == "code_allgather" else spmd_axis
            QYs = jax.vmap(slot_decode_up, in_axes=(0, 0, None),
                           spmd_axis_name=up_axis)(
                msgs_up, q_keys, state.server)

            server_new = {
                k: ((state.server[k].astype(jnp.float32)
                     + jnp.sum(QYs[k].astype(jnp.float32), 0)) / denom
                    ).astype(state.server[k].dtype)
                for k in state.server}

            # ---- server -> clients: ONE Enc(X_t), per-client decode ------
            hints_down = {
                k: 2.0 * jnp.max(jax.vmap(
                    lambda q: jnp.linalg.norm(
                        (q - state.server[k]).astype(jnp.float32).ravel()))(
                    QYs[k]))
                for k in state.server}
            k_srv = jax.random.fold_in(k_q, n_slots + 7)
            msg_srv = tree_encode(quant_down, k_srv, state.server, hints_down)

            if unroll_slots:
                cls = [slot_update(sl(state.clients, i), sl(Ys, i), k_srv,
                                   msg_srv, denom) for i in range(n_slots)]
                clients_new = {k: jnp.stack([c[k] for c in cls], 0)
                               for k in state.server}
            else:
                clients_new = jax.vmap(slot_update,
                                       in_axes=(0, 0, None, None, None),
                                       spmd_axis_name=spmd_axis)(
                    state.clients, Ys, k_srv, msg_srv, denom)

        qerr = sum(jnp.sum(jnp.square((QYs[k] - Ys[k]).astype(jnp.float32)))
                   for k in state.server) / n_slots

        new_state = TrainState(server=server_new, clients=clients_new,
                               t=state.t + 1)
        metrics = {"h_steps_mean": jnp.mean(h_steps.astype(jnp.float32)),
                   "quant_err_sq": qerr, **counters}
        return new_state, metrics

    state_spec, state_sh = abstract_train_state(cfg, mesh, fed_mode)
    in_ax = input_axes(cfg, shape)
    batch_sh = {k: NamedSharding(
        mesh, pspec_for(v.shape, in_ax[k], rules, mesh))
        for k, v in input_specs(cfg, shape, n_slots=n_slots,
                                local_steps=K).items()}
    key_sh = NamedSharding(mesh, P())
    return train_step, state_spec, (state_sh, batch_sh, key_sh)


# ---------------------------------------------------------------------------
# prefill / serve steps (inference of the server model)
# ---------------------------------------------------------------------------

def build_prefill_step(cfg: ModelConfig, mesh, shape: ShapeConfig):
    rules = rules_for_mode("client_dp")
    enc = enc_len_for(shape) if cfg.encdec else 0

    def prefill_step(params, batch):
        cache0 = init_cache(cfg, shape.global_batch, shape.seq_len,
                            abstract=False, enc_len=enc)
        logits, cache, _ = forward(cfg, params, batch, cache=cache0,
                                   write_pos=0)
        return logits[:, -1], cache

    spec, axes = abstract_lm(cfg)
    p_sh = {k: NamedSharding(mesh, pspec_for(v.shape, axes[k], rules, mesh))
            for k, v in spec.items()}
    in_ax = input_axes(cfg, shape)
    b_sh = {k: NamedSharding(mesh, pspec_for(v.shape, in_ax[k], rules, mesh))
            for k, v in input_specs(cfg, shape).items()}
    return prefill_step, spec, (p_sh, b_sh)


def build_serve_step(cfg: ModelConfig, mesh, shape: ShapeConfig):
    """One-token decode against a seq_len-deep cache (decode shapes)."""
    rules = rules_for_mode("client_dp")

    def serve_step(params, cache, token, pos):
        logits, cache = decode_step(cfg, params, token, pos, cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache

    spec, axes = abstract_lm(cfg)
    p_sh = {k: NamedSharding(mesh, pspec_for(v.shape, axes[k], rules, mesh))
            for k, v in spec.items()}
    cache_spec, c_axes = abstract_cache(cfg, shape)
    c_sh = {k: NamedSharding(mesh, pspec_for(v.shape, c_axes[k], rules, mesh))
            for k, v in cache_spec.items()}
    tok_sh = NamedSharding(mesh, pspec_for((shape.global_batch, 1),
                                           ("batch", None), rules, mesh))
    pos_sh = NamedSharding(mesh, P())
    return serve_step, spec, cache_spec, (p_sh, c_sh, tok_sh, pos_sh)
