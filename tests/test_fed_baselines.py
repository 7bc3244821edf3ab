"""Baselines the paper compares against: FedAvg, FedBuff, sequential."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig
from repro.core import FedAvg, FedBuff, Sequential
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch
from repro.models.mlp import init_mlp_classifier, mlp_loss


def _setup(fed, seed=0):
    part, test = make_federated_classification(seed, fed.n_clients, d=16,
                                               n_classes=4)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(seed), 16, 32, 4)
    bf = lambda d, k: client_batch(k, d, 16)
    return part, test, params0, bf


def test_fedavg_converges_and_waits_for_slowest():
    fed = FedConfig(n_clients=8, s=4, local_steps=4, lr=0.3)
    part, test, params0, bf = _setup(fed)
    alg = FedAvg(fed=fed, loss_fn=mlp_loss, template=params0, batch_fn=bf)
    st = alg.init(params0)
    key = jax.random.PRNGKey(1)
    for _ in range(40):
        key, sub = jax.random.split(key)
        st, m = alg.round(st, part, sub)
    loss, metr = mlp_loss(alg.eval_params(st), test)
    assert float(metr["acc"]) > 0.6
    # round time must exceed the expected K steps of a FAST client — the
    # synchronous server waits for stragglers
    assert float(st.sim_time) / 40 > fed.local_steps / fed.lam_fast


def test_fedbuff_runs_and_improves():
    fed = FedConfig(n_clients=8, s=4, local_steps=4, lr=0.3)
    part, test, params0, bf = _setup(fed)
    alg = FedBuff(fed=fed, loss_fn=mlp_loss, template=params0, batch_fn=bf,
                  buffer_size=4, server_lr=0.5)
    hist = alg.run(params0, part, jax.random.PRNGKey(2), total_time=600.0,
                   eval_every=100.0,
                   eval_fn=lambda p: float(mlp_loss(p, test)[0]))
    assert len(hist) >= 4
    assert hist[-1][1] < hist[0][1]


def test_fedbuff_quantized():
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.2, bits=8)
    part, test, params0, bf = _setup(fed)
    alg = FedBuff(fed=fed, loss_fn=mlp_loss, template=params0, batch_fn=bf,
                  buffer_size=3, uplink="scalar")
    hist = alg.run(params0, part, jax.random.PRNGKey(3), total_time=300.0,
                   eval_every=100.0,
                   eval_fn=lambda p: float(mlp_loss(p, test)[0]))
    assert np.isfinite(hist[-1][1])


def test_sequential_baseline():
    fed = FedConfig(n_clients=4, s=1, local_steps=1, lr=0.2)
    part, test, params0, bf = _setup(fed)
    alg = Sequential(fed=fed, loss_fn=mlp_loss, template=params0, batch_fn=bf)
    st = alg.init(params0)
    key = jax.random.PRNGKey(4)
    l0 = float(mlp_loss(alg.eval_params(st), test)[0])
    for _ in range(150):
        key, sub = jax.random.split(key)
        st, _ = alg.round(st, part, sub)
    assert float(mlp_loss(alg.eval_params(st), test)[0]) < l0
