"""Rotated-space compression pipeline: equivalence, rotation audit, registry.

The pipeline (repro.compression.pipeline) restructures the QuAFL exchange so
each vector is rotated once per round. These tests pin it three ways:

  * a full ``QuAFL.round`` through the fused rotated-space path must match
    the per-message materialize-everything composition (same keys/noise/γ)
    to fp32 tolerance,
  * the trace-time rotation counter must report exactly s+1 forward and
    s+1 inverse full-model rotations per round (seed spent ~5s+1; the first
    fused version spent s+2 before the downlink became an elementwise
    quantize of the cached rotated server),
  * every registered backend must agree on codes and decodes
    (``perf_smoke``: the fast sanity slice CI runs on every commit).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression import ExchangePipeline, get_backend, make_codec
from repro.compression.rotation import pad_len
from repro.configs.base import FedConfig
from repro.core import QuAFL
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch
from repro.models.mlp import init_mlp_classifier, mlp_loss


def _setup(fed, seed=0, **kw):
    part, test = make_federated_classification(seed, fed.n_clients, d=16,
                                               n_classes=4)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(seed), 16, 32, 4)
    alg = QuAFL(fed=fed, loss_fn=mlp_loss, template=params0,
                batch_fn=lambda d_, k: client_batch(k, d_, 16), **kw)
    return alg, alg.init(params0), part


# ---------------------------------------------------------------------------
# equivalence: fused rotated-space round == per-message composition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("avg_mode", ["both", "server_only", "client_only"])
def test_quafl_round_pipeline_matches_reference(avg_mode):
    fed = FedConfig(n_clients=8, s=4, local_steps=2, lr=0.2, bits=8)
    key = jax.random.PRNGKey(7)
    alg_p, st_p, part = _setup(fed, avg_mode=avg_mode)
    alg_r, st_r, _ = _setup(fed, avg_mode=avg_mode,
                            exchange_impl="reference")
    for _ in range(3):
        key, sub = jax.random.split(key)
        st_p, m_p = alg_p.round(st_p, part, sub)
        st_r, m_r = alg_r.round(st_r, part, sub)
    np.testing.assert_allclose(np.asarray(st_p.server),
                               np.asarray(st_r.server), atol=2e-5)
    np.testing.assert_allclose(np.asarray(st_p.clients),
                               np.asarray(st_r.clients), atol=2e-5)
    np.testing.assert_allclose(float(m_p["quant_err"]),
                               float(m_r["quant_err"]), rtol=1e-3)


def test_pipeline_exchange_matches_reference_directly():
    """quafl_round vs quafl_round_reference on raw vectors, both backends."""
    key = jax.random.PRNGKey(3)
    d, s = 5000, 6
    server = jax.random.normal(key, (d,))
    Y = server[None] + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 1), (s, d))
    hints = jnp.linalg.norm(Y - server[None], axis=1) + 1e-8
    ref = ExchangePipeline(bits=8, backend="jnp").quafl_round_reference(
        key, server, Y, hints)
    for backend in ("jnp", "pallas_interpret"):
        out = ExchangePipeline(bits=8, backend=backend).quafl_round(
            key, server, Y, hints)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[1]),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# rotation audit: s+1 forward, s+1 inverse per round (seed: ~5s+1)
# ---------------------------------------------------------------------------

def test_rotation_count_per_round():
    s = 4
    fed = FedConfig(n_clients=8, s=s, local_steps=1, lr=0.1)
    alg, st, part = _setup(fed)
    assert alg.pipeline is not None
    alg.pipeline.stats.reset()
    st, _ = alg.round(st, part, jax.random.PRNGKey(0))   # one trace
    assert alg.pipeline.stats.fwd == s + 1, alg.pipeline.stats
    assert alg.pipeline.stats.inv == s + 1, alg.pipeline.stats
    # further rounds reuse the trace: the count is structural, per round
    alg.pipeline.stats.reset()
    st, _ = alg.round(st, part, jax.random.PRNGKey(1))
    assert alg.pipeline.stats.fwd == 0 and alg.pipeline.stats.inv == 0


# ---------------------------------------------------------------------------
# backend registry (perf_smoke: must stay well under a minute)
# ---------------------------------------------------------------------------

@pytest.mark.perf_smoke
def test_backend_registry_names():
    for name in ("jnp", "pallas_interpret", "pallas"):
        assert get_backend(name).name == name
    with pytest.raises(ValueError):
        get_backend("cuda")
    with pytest.raises(ValueError):
        make_codec("lattice", backend="bogus").encode(
            jax.random.PRNGKey(0), jnp.ones(8), 1.0)


@pytest.mark.perf_smoke
@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_backend_quantizer_roundtrip(backend):
    d = 3000
    q = make_codec("lattice", bits=8, backend=backend)
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (d,))
    ref = x + 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (d,))
    msg = q.encode(key, x, jnp.linalg.norm(x - ref))
    xh = q.decode(key, msg, ref)
    err = float(jnp.linalg.norm(xh - x))
    assert err <= float(msg.gamma) * np.sqrt(pad_len(d)) * 1.01


@pytest.mark.perf_smoke
def test_backends_agree_on_codes_and_decode():
    d = 3000
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (d,))
    ref = x + 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (d,))
    hint = jnp.linalg.norm(x - ref)
    msgs, outs = {}, {}
    for backend in ("jnp", "pallas_interpret"):
        q = make_codec("lattice", bits=8, backend=backend)
        msgs[backend] = q.encode(key, x, hint)
        outs[backend] = q.decode(key, msgs[backend], ref)
    a, b = msgs["jnp"], msgs["pallas_interpret"]
    assert float(a.gamma) == float(b.gamma)
    # stochastic-rounding boundaries may flip under a different matmul
    # association; anything beyond a stray ulp-flip is a real bug
    agree = float(jnp.mean((a.codes == b.codes).astype(jnp.float32)))
    assert agree >= 0.999, agree
    np.testing.assert_allclose(np.asarray(outs["jnp"]),
                               np.asarray(outs["pallas_interpret"]),
                               atol=2.5 * float(a.gamma))


@pytest.mark.perf_smoke
def test_fedconfig_backend_reaches_pipeline():
    fed = FedConfig(n_clients=4, s=2, local_steps=1,
                    kernel_backend="pallas_interpret")
    alg, _, _ = _setup(fed)
    assert alg.pipeline.backend == "pallas_interpret"
    assert alg.codec_up.backend == "pallas_interpret"
    assert alg.codec_down.backend == "pallas_interpret"
