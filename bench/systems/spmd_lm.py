"""A language model trained through the ``spmd`` registry algorithm: one
QuAFL round per call of ``SpmdAlgorithm.round`` (the eager ``simulate()``
loop, state donated), on a mesh of one client slot per data slice.

Set-up makes the weights on the device from the seed, builds the algorithm
as ``make_algorithm`` does, and drives its first rounds (the check rounds,
which also compile and warm up the round) through the window's own call
and feed. The readings of those rounds, per-leaf norms of the change of
the server and client models and the uplink's quantization error, are
what the reference has to reproduce.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import traffic as traffic_mod
from bench.harness import DeviceCell
from bench.systems import spmd_lm_reference as reference


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file. Refuses what
    the program cannot run as the file states it."""
    from repro.configs.base import ATTN_FULL, LayerSpec, ModelConfig
    if (cfg["layer_norm_type"] != "rms" or cfg["layer_norm_with_affine"]
            or cfg["embedding_multiplier"] != "sqrt_d_model"
            or not cfg["weight_tying"] or cfg["include_bias"]
            or cfg["layer_norm_eps"] != 1e-6):
        raise ValueError("the program runs a non-parametric RMSNorm (eps "
                         "1e-6), no biases and a tied embedding scaled by "
                         "sqrt(d_model); the configuration states otherwise")
    d, h = cfg["d_model"], cfg["n_heads"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense", source=cfg["source"],
        n_layers=cfg["n_layers"], d_model=d, n_heads=h, n_kv_heads=h,
        head_dim=d // h, d_ff=cfg["mlp_ratio"] * d // 2,
        vocab_size=cfg["embedding_size"],
        schedule=(LayerSpec(attn=ATTN_FULL),), nonparametric_ln=True,
        tie_embeddings=True, rope_theta=float(cfg["rope_theta"]),
        dtype=cfg["compute_dtype"], param_dtype=cfg["param_dtype"])


def fed_config(cfg: dict, traffic: dict):
    from repro.configs.base import FedConfig
    f = cfg["fed"]
    return FedConfig(n_clients=f["n_clients"], s=f["s"],
                     local_steps=traffic["local_steps"], lr=f["lr"],
                     weighted=f["weighted"], bits=f["bits"],
                     kernel_backend=f["kernel_backend"],
                     lam_fast=f["lam_fast"], swt=f["swt"], sit=f["sit"],
                     transport=f["transport"])


class Cell(DeviceCell):
    rounds_per_call = 1

    def __init__(self, cfg: dict, traffic: dict, model, seed_key):
        self.cfg, self.traffic, self.model = cfg, traffic, model
        self.seed_key = seed_key
        self.k_weights, self.k_data, self.k_run = (
            jax.random.fold_in(seed_key, i) for i in range(3))
        self.seq = traffic["inputs"]["seq"]
        self.batch = traffic["batch"]
        n = cfg["fed"]["n_clients"]
        tokens = n * traffic["local_steps"] * self.batch * self.seq
        self.flops_per_round = tokens * model.flops_per_token(cfg, self.seq)

    # -- set-up -----------------------------------------------------------
    def setup(self):
        from repro.fed import make_algorithm
        from repro.utils.compat import make_mesh
        cfg, tr = self.cfg, self.traffic
        mcfg = model_config(cfg)
        fed = fed_config(cfg, tr)
        params = jax.jit(lambda k: self.model.weights(cfg, k))(self.k_weights)
        self.data = jax.jit(lambda k: traffic_mod.make(
            k, tr, n_clients=fed.n_clients, vocab=cfg["vocab_size"]))(
            self.k_data)
        template = jax.eval_shape(lambda: params)
        self.alg = make_algorithm(
            "spmd", fed, loss_fn=None, template=template, batch_fn=None,
            cfg=mcfg, mesh=make_mesh(tuple(cfg["fed"]["mesh"]),
                                     ("data", "model")),
            batch=self.batch, seq=self.seq, remat=cfg["fed"]["remat"])
        self.state = self.alg.init(params)
        del params
        self.key = self.k_run
        change = jax.jit(self._change_norms)
        q, update = [], None
        for r in range(tr["check_rounds"]):
            self.prepare()
            m = self.step()
            q.append(m["quant_err_sq"])
            if r == 0:
                update = change(self.state.train, self.k_weights)
        last = change(self.state.train, self.k_weights)
        self.check = jax.device_get({
            "quant_err": [jnp.sqrt(x) for x in q],
            "update": update["server"], "change": last})

    def _change_norms(self, train, k_weights):
        """Per-leaf norms of the server's and each client's change since
        the start, the start made again from the seed."""
        w0 = self.model.weights(self.cfg, k_weights)
        out = {"server": {k: jnp.linalg.norm(train.server[k] - w0[k])
                          for k in w0}}
        for i in range(train.clients[next(iter(w0))].shape[0]):
            out[f"client{i}"] = {k: jnp.linalg.norm(train.clients[k][i]
                                                    - w0[k]) for k in w0}
        return out

    # -- the window ---------------------------------------------------------
    def prepare(self):
        self.key, self.sub = jax.random.split(self.key)

    def step(self):
        self.state, metrics = self.alg.round(self.state, self.data, self.sub)
        return metrics

    # -- correctness ----------------------------------------------------------
    def reference(self, precision: str = "f32", fault: str | None = None):
        return reference.run(self.cfg, self.traffic, self.model,
                             k_weights=self.k_weights, k_data=self.k_data,
                             k_run=self.k_run, precision=precision,
                             fault=fault)
