"""QuAFL algorithm invariants + convergence (paper Alg. 1, §3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core import FedAvg, QuAFL, Sequential, expected_steps, client_speeds
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch
from repro.models.mlp import init_mlp_classifier, mlp_loss


def _setup(fed, seed=0, iid=True, **kw):
    part, test = make_federated_classification(seed, fed.n_clients, d=16,
                                               n_classes=4, iid=iid)
    key = jax.random.PRNGKey(seed)
    params0, _ = init_mlp_classifier(key, 16, 32, 4)
    alg = QuAFL(fed=fed, loss_fn=mlp_loss, template=params0,
                batch_fn=lambda d, k: client_batch(k, d, 16), **kw)
    return alg, alg.init(params0), part, test, key


def test_mean_preservation_no_steps_no_quant():
    """With lr=0 and no quantization, a round is pure (s+1)-averaging and
    the model mean μ_t is EXACTLY preserved (paper §2.2 'Model Averaging')."""
    fed = FedConfig(n_clients=8, s=3, local_steps=2, lr=0.0,
                    codec_up="identity", codec_down="identity")
    alg, st, part, _, key = _setup(fed)
    # diverge the clients artificially
    st = st.with_clients(st.clients + jax.random.normal(
        key, st.clients.shape))
    mu0 = (st.server + jnp.sum(st.clients, 0)) / (fed.n_clients + 1)
    st2, _ = alg.round(st, part, key)
    mu1 = (st2.server + jnp.sum(st2.clients, 0)) / (fed.n_clients + 1)
    np.testing.assert_allclose(np.asarray(mu1), np.asarray(mu0), atol=1e-5)


def test_clients_contract_towards_server():
    """The (s+1)-averaging strictly decreases the potential Φ when lr=0."""
    fed = FedConfig(n_clients=6, s=6, local_steps=1, lr=0.0,
                    codec_up="identity", codec_down="identity")
    alg, st, part, _, key = _setup(fed)
    st = st.with_clients(st.clients + jax.random.normal(
        key, st.clients.shape))

    def phi(s):
        mu = (s.server + jnp.sum(s.clients, 0)) / (fed.n_clients + 1)
        return float(jnp.sum((s.clients - mu) ** 2) +
                     jnp.sum((s.server - mu) ** 2))

    p0 = phi(st)
    st2, _ = alg.round(st, part, key)
    assert phi(st2) < p0


@pytest.mark.parametrize("codec", ["lattice", "scalar", "identity"])
def test_quafl_converges(codec):
    fed = FedConfig(n_clients=8, s=4, local_steps=4, lr=0.3,
                    codec_up=codec, codec_down=codec, bits=10, swt=10.0)
    alg, st, part, test, key = _setup(fed)
    loss0, _ = mlp_loss(alg.eval_params(st), test)
    for _ in range(60):
        key, sub = jax.random.split(key)
        st, m = alg.round(st, part, sub)
    loss1, metr = mlp_loss(alg.eval_params(st), test)
    assert float(loss1) < 0.7 * float(loss0), (float(loss0), float(loss1))
    assert float(metr["acc"]) > 0.5


def test_quafl_noniid_converges():
    fed = FedConfig(n_clients=8, s=4, local_steps=4, lr=0.3, bits=10)
    alg, st, part, test, key = _setup(fed, iid=False)
    for _ in range(80):
        key, sub = jax.random.split(key)
        st, _ = alg.round(st, part, sub)
    loss, metr = mlp_loss(alg.eval_params(st), test)
    assert float(metr["acc"]) > 0.4


def test_mean_model_tracks_server():
    """Corollary 3.3: server stays close to the mean of local models."""
    fed = FedConfig(n_clients=8, s=4, local_steps=2, lr=0.1)
    alg, st, part, test, key = _setup(fed)
    for _ in range(30):
        key, sub = jax.random.split(key)
        st, _ = alg.round(st, part, sub)
    mu = (st.server + jnp.sum(st.clients, 0)) / (fed.n_clients + 1)
    rel = float(jnp.linalg.norm(st.server - mu) / jnp.linalg.norm(mu))
    assert rel < 0.2, rel


def test_weighted_dampening():
    fed = FedConfig(n_clients=10, s=5, local_steps=20, weighted=True,
                    swt=2.0, sit=1.0, slow_frac=0.5)
    lam = client_speeds(fed, 10)
    H = expected_steps(fed, lam)
    alg, *_ = _setup(fed)
    # eta_i * H_i is constant across clients (paper §3.3)
    prod = alg.eta_i * alg.H
    np.testing.assert_allclose(prod, prod[0], rtol=1e-5)


def test_h_can_be_zero():
    """Slow clients polled early can contribute zero steps (paper §2.2)."""
    fed = FedConfig(n_clients=16, s=16, local_steps=5, swt=0.1, sit=0.1,
                    slow_frac=1.0, lam_slow=0.01)
    alg, st, part, _, key = _setup(fed)
    st, m = alg.round(st, part, key)
    assert float(m["h_zero_frac"]) > 0.5


def test_bits_accounting_monotone():
    fed = FedConfig(n_clients=6, s=3, local_steps=1, bits=8)
    alg, st, part, _, key = _setup(fed)
    st1, m = alg.round(st, part, key)
    st2, _ = alg.round(st1, part, key)
    assert float(st2.bits_sent) == 2 * float(st1.bits_sent) > 0
    # lattice: (s+1) messages of d_pad*b (+ γ) bits per round
    assert float(m["bits"]) == (fed.s + 1) * alg.codec_up.message_bits(alg.d)


@pytest.mark.parametrize("mode", ["both", "server_only", "client_only"])
def test_averaging_variants_run(mode):
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.2)
    alg, st, part, test, key = _setup(fed, avg_mode=mode)
    for _ in range(10):
        key, sub = jax.random.split(key)
        st, _ = alg.round(st, part, sub)
    loss, _ = mlp_loss(alg.eval_params(st), test)
    assert np.isfinite(float(loss))
