"""A flat model trained by the ``quafl`` registry algorithm through the
scanned engine: each call is ``RoundEngine.run_chunk`` over
``QuAFL.device_round``, a fixed number of rounds in one jitted
``lax.scan`` (the ``simulate(scan_chunk=L)`` path), state donated.

Set-up makes the weights and every client's data on the device from the
seed, builds the algorithm as ``make_algorithm`` does, and runs the first
chunk (which compiles and warms the chunk program) through the window's
own call. Its readings, per round the uplink's relative decode error, and
per leaf the change of the server and of each client's row of the
population (so a row gathered or scattered in the wrong place shows), are
what the reference has to reproduce.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as traffic_mod
from bench.harness import DeviceCell
from bench.systems import quafl_flat_reference as reference


def fed_config(cfg: dict, traffic: dict):
    from repro.configs.base import FedConfig
    f = cfg["fed"]
    return FedConfig(n_clients=f["n_clients"], s=f["s"],
                     local_steps=traffic["local_steps"], lr=f["lr"],
                     weighted=f["weighted"], bits=f["bits"],
                     kernel_backend=f["kernel_backend"],
                     participation=f["participation"],
                     slow_frac=f["slow_frac"], lam_fast=f["lam_fast"],
                     lam_slow=f["lam_slow"], swt=f["swt"], sit=f["sit"])


def leaf_slices(cfg, model):
    """(leaf, start, stop) of each leaf in the flat vector, in the order of
    the tree's sorted keys."""
    out, off = [], 0
    for name, shape in sorted(model.shapes(cfg).items()):
        size = int(np.prod(shape))
        out.append((name, off, off + size))
        off += size
    return out


def change_norms(slices, server, clients, x0):
    """Per-leaf norms of the server's change and of each client row's."""
    return {"server": {k: jnp.linalg.norm(server[a:b] - x0[a:b])
                       for k, a, b in slices},
            "clients": {k: jnp.linalg.norm(clients[:, a:b] - x0[None, a:b],
                                           axis=1)
                        for k, a, b in slices}}


class Cell(DeviceCell):
    flops_per_round = None

    def __init__(self, cfg: dict, traffic: dict, model, seed_key):
        self.cfg, self.traffic, self.model = cfg, traffic, model
        self.k_weights, self.k_data, self.k_run = (
            jax.random.fold_in(seed_key, i) for i in range(3))
        self.rounds_per_call = cfg["fed"]["scan_chunk"]
        self.slices = leaf_slices(cfg, model)

    def setup(self):
        from repro.data.synthetic import client_batch
        from repro.fed import make_algorithm
        from repro.fed.engine import RoundEngine
        from repro.models.mlp import mlp_loss
        cfg, tr = self.cfg, self.traffic
        fed = fed_config(cfg, tr)
        params = jax.jit(lambda k: self.model.weights(cfg, k))(self.k_weights)
        self.data = jax.jit(lambda k: traffic_mod.make(
            k, tr, n_clients=fed.n_clients, d=cfg["d_in"],
            n_classes=cfg["n_classes"]))(self.k_data)
        batch = tr["batch"]
        self.alg = make_algorithm(
            "quafl", fed, loss_fn=mlp_loss, template=params,
            batch_fn=lambda dd, k: client_batch(k, dd, batch))
        self.engine = RoundEngine(self.alg)
        self.state = self.alg.init(params)
        x0 = jnp.copy(self.state.server)   # the chunk donates the state
        self.key = self.k_run
        metrics = self.step()
        norms = jax.jit(lambda s, c, x: change_norms(self.slices, s, c, x))(
            self.state.server, self.state.clients, x0)
        self.check = jax.device_get({"quant_err": metrics["quant_err"],
                                     "change": norms})
        self.check["quant_err"] = list(self.check["quant_err"])

    def prepare(self):
        pass

    def step(self):
        self.key, self.state, metrics = self.engine.run_chunk(
            self.state, self.data, self.key, self.rounds_per_call)
        return metrics

    def reference(self, precision: str = "f32", fault: str | None = None):
        return reference.run(self.cfg, self.traffic, self.model, self.slices,
                             k_weights=self.k_weights, k_data=self.k_data,
                             k_run=self.k_run, rounds=self.rounds_per_call,
                             precision=precision, fault=fault)
