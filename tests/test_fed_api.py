"""Unified federated-algorithm API: registry, protocol, simulate, metrics.

Pins the api_redesign four ways:

  * every registry name builds, satisfies the FedAlgorithm protocol, and a
    round through the registry object is BIT-IDENTICAL to the legacy class
    (the registry is thin plumbing, not a reimplementation),
  * the event-driven FedBuff round() path and its legacy run() entry point
    drive the same completion stream (same seeds -> same server),
  * the standardized metrics schema (sim_time / bits_up / bits_down /
    h_steps_mean / quant_err) holds for every algorithm, and the split bit
    counters match ``tree_bits`` per direction,
  * simulate()/compare() respect round, sim-time, and bits budgets.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core import (AdaptiveBits, AdaptiveQuAFL, FedAvg, FedBuff, QuAFL,
                        QuaflScaffold, Sequential)
from repro.core.transport import tree_bits
from repro.compression import make_codec
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch
from repro.fed import (FedAlgorithm, METRIC_KEYS, compare, make_algorithm,
                       normalize_metrics, registered_algorithms, simulate)
from repro.models.mlp import init_mlp_classifier, mlp_loss
from repro.utils.tree import tree_flatten_vector

ALL_NAMES = ("quafl", "fedavg", "fedbuff", "sequential", "quafl_scaffold",
             "adaptive_quafl", "fedbuff_device", "spmd",
             "compressed_fedavg")

# spmd wraps the mesh-sharded LM train step: it needs a ModelConfig and
# token data, so the MLP-task smoke loops skip it (tests/test_engine.py
# covers it end to end through simulate()).
_MLP_NAMES = tuple(n for n in ALL_NAMES if n != "spmd")

LEGACY = {"quafl": QuAFL, "fedavg": FedAvg, "sequential": Sequential,
          "quafl_scaffold": QuaflScaffold}


def _setup(fed, seed=0, iid=True, d=16, hidden=32, classes=4):
    part, test = make_federated_classification(seed, fed.n_clients, d=d,
                                               n_classes=classes, iid=iid)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(seed), d, hidden,
                                     classes)
    bf = lambda dd, k: client_batch(k, dd, d)
    return part, test, params0, bf


_SMOKE_CACHE = {}


def _smoke_setup():
    """Shared tiny task for the perf_smoke tests (built once per session)."""
    if not _SMOKE_CACHE:
        fed = FedConfig(n_clients=2, s=1, local_steps=1, lr=0.2, bits=6,
                        codec_up="scalar", codec_down="scalar")
        _SMOKE_CACHE["v"] = (fed,) + _setup(fed, d=8, hidden=8, classes=2)
    return _SMOKE_CACHE["v"]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

@pytest.mark.perf_smoke
def test_registry_names_and_protocol():
    assert registered_algorithms() == ALL_NAMES
    fed, part, test, params0, bf = _smoke_setup()
    for name in _MLP_NAMES:
        alg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                             batch_fn=bf)
        assert isinstance(alg, FedAlgorithm), name
    with pytest.raises(ValueError):
        make_algorithm("sgd", fed, loss_fn=mlp_loss, template=params0,
                       batch_fn=bf)


@pytest.mark.perf_smoke
def test_every_registered_algorithm_steps_once():
    """Instantiate and step EVERY registry algorithm once (CI smoke).

    Deliberately minimal shapes (1 sampled client, 1 local step, the
    scalar codec both ways — the lattice pipeline would pad to the 16k
    Hadamard block): the budget is six
    XLA compiles in <10s, and this test only checks the registry ->
    protocol -> metrics-schema plumbing. The jitted lattice paths are
    pinned by the non-smoke tests here and by test_pipeline.py."""
    fed, part, test, params0, bf = _smoke_setup()
    for name in _MLP_NAMES:
        kw = ({"buffer_size": 1}
              if name in ("fedbuff", "fedbuff_device") else {})
        alg = make_algorithm(name, fed, loss_fn=mlp_loss,
                             template=params0, batch_fn=bf, **kw)
        state, m = alg.round(alg.init(params0), part,
                             jax.random.PRNGKey(1))
        norm = normalize_metrics(m)
        for k in METRIC_KEYS:
            assert k in m, (name, k)
            assert np.isfinite(norm[k]), (name, k, norm[k])
        assert np.all(np.isfinite(np.asarray(
            tree_flatten_vector(alg.eval_params(state))))), name


# ---------------------------------------------------------------------------
# bit-for-bit equivalence: registry object == legacy class
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(LEGACY))
def test_registry_round_matches_legacy_bitwise(name):
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.3, bits=8)
    part, test, params0, bf = _setup(fed)
    legacy = LEGACY[name](fed=fed, loss_fn=mlp_loss, template=params0,
                          batch_fn=bf)
    reg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    sl, sr = legacy.init(params0), reg.init(params0)
    key = jax.random.PRNGKey(3)
    for _ in range(3):
        key, sub = jax.random.split(key)
        sl, ml = legacy.round(sl, part, sub)
        sr, mr = reg.round(sr, part, sub)
    fl = tree_flatten_vector(legacy.eval_params(sl))
    fr = tree_flatten_vector(reg.eval_params(sr))
    np.testing.assert_array_equal(np.asarray(fl), np.asarray(fr))
    assert normalize_metrics(ml) == normalize_metrics(mr)


def test_fedbuff_round_path_matches_run_path():
    """The protocol round() (advance-to-flush) and the legacy run() entry
    point drive the same single-completion step: same key -> identical
    server after the same flushes."""
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.2)
    part, test, params0, bf = _setup(fed)
    key = jax.random.PRNGKey(11)
    mk = lambda: make_algorithm("fedbuff", fed, loss_fn=mlp_loss,
                                template=params0, batch_fn=bf,
                                buffer_size=3, server_lr=0.5)
    alg = mk()
    state = alg.init(params0)
    for _ in range(3):
        state, m = alg.round(state, part, key)
        assert m["buffer_flushes"] == 1.0
    t_end = float(state.sim_time)

    # evals fire BEFORE the event at their grid time, so stretch total_time
    # past the last flush by a couple of grid steps: the tail evals then
    # report the post-flush server (one stray completion cannot flush again
    # with an empty buffer of size 3, so the server stays put).
    dt = max(t_end / 64, 1e-2)
    hist = mk().run(params0, part, key, total_time=t_end + 2 * dt,
                    eval_every=dt,
                    eval_fn=lambda p: np.asarray(tree_flatten_vector(p)))
    np.testing.assert_array_equal(hist[-1][1], np.asarray(state.server))


def test_adaptive_registry_matches_legacy_wrapper():
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.3, bits=10)
    part, test, params0, bf = _setup(fed)
    reg = make_algorithm("adaptive_quafl", fed, loss_fn=mlp_loss,
                         template=params0, batch_fn=bf)
    legacy = AdaptiveQuAFL(
        fed, lambda f: QuAFL(fed=f, loss_fn=mlp_loss, template=params0,
                             batch_fn=bf), params0)
    state = reg.init(params0)
    key = jax.random.PRNGKey(5)
    for _ in range(6):
        key, sub = jax.random.split(key)
        state, _ = reg.round(state, part, sub)
        legacy.round(part, sub)
    assert list(state.trace) == legacy.bits_trace
    np.testing.assert_array_equal(
        np.asarray(tree_flatten_vector(reg.eval_params(state))),
        np.asarray(tree_flatten_vector(legacy.eval_params())))


# ---------------------------------------------------------------------------
# split bit accounting vs tree_bits, per direction
# ---------------------------------------------------------------------------

def test_quafl_bits_split_matches_tree_bits():
    fed = FedConfig(n_clients=6, s=3, local_steps=1, bits=8)
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    st0 = alg.init(params0)
    st1, m = alg.round(st0, part, jax.random.PRNGKey(0))
    msg_tree = {"model": jnp.zeros((alg.d,))}   # one flat model message
    per_msg = tree_bits(alg.codec_up, msg_tree)
    # s uplink messages, ONE downlink broadcast
    assert float(st1.bits_up) == fed.s * per_msg == float(m["bits_up"])
    assert float(st1.bits_down) == per_msg == float(m["bits_down"])
    assert float(st1.bits_sent) == float(st1.bits_up) + float(st1.bits_down)


def test_fedavg_bits_split_matches_tree_bits():
    fed = FedConfig(n_clients=6, s=3, local_steps=1)
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("fedavg", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    st1, m = alg.round(alg.init(params0), part, jax.random.PRNGKey(0))
    per_msg = tree_bits(make_codec("identity"), {"m": jnp.zeros((alg.d,))})
    # uncompressed model each way for each of the s sampled clients
    assert float(st1.bits_up) == fed.s * per_msg == float(m["bits_up"])
    assert float(st1.bits_down) == fed.s * per_msg == float(m["bits_down"])


def test_scaffold_bits_split_is_doubled_quafl():
    fed = FedConfig(n_clients=6, s=3, local_steps=1, bits=8)
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("quafl_scaffold", fed, loss_fn=mlp_loss,
                         template=params0, batch_fn=bf)
    st1, m = alg.round(alg.init(params0), part, jax.random.PRNGKey(0))
    per_msg = tree_bits(alg.codec_up, {"m": jnp.zeros((alg.d,))})
    # model + control variate ride the exchange in both directions
    assert float(st1.base.bits_up) == 2 * fed.s * per_msg
    assert float(st1.base.bits_down) == 2 * per_msg
    assert float(m["bits_up"]) == 2 * fed.s * per_msg


def test_fedbuff_bits_split_per_flush():
    fed = FedConfig(n_clients=4, s=2, local_steps=1, bits=8)
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("fedbuff", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf, buffer_size=3, uplink="lattice")
    st1, m = alg.round(alg.init(params0), part, jax.random.PRNGKey(2))
    per_up = tree_bits(alg.codec_up, {"m": jnp.zeros((alg.d,))})
    # one quantized delta up + one fp32 restart model down per completion
    assert float(m["bits_up"]) == 3 * per_up
    assert float(m["bits_down"]) == 3 * alg.d * 32
    assert float(st1.bits_sent) == float(m["bits_up"]) + float(m["bits_down"])


# ---------------------------------------------------------------------------
# simulate / compare budgets
# ---------------------------------------------------------------------------

def test_simulate_round_and_time_budgets():
    fed = FedConfig(n_clients=6, s=3, local_steps=1, lr=0.2)
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    tr = simulate(alg, params0, part, jax.random.PRNGKey(1), rounds=5,
                  eval_every=2)
    assert tr.rounds == 5 and tr.final["round"] == 5
    # sim-time budget: quafl rounds last swt+sit=11s
    tr2 = simulate(alg, params0, part, jax.random.PRNGKey(1),
                   until_sim_time=50.0)
    assert tr2.rounds == 5 and tr2.final["sim_time"] >= 50.0
    with pytest.raises(ValueError):
        simulate(alg, params0, part, jax.random.PRNGKey(1))


def test_compare_equal_bits_budget():
    """Equal-bits comparison: every algorithm stops once its cumulative
    up+down bits cross the budget — QuAFL fits many more rounds in it."""
    fed = FedConfig(n_clients=6, s=3, local_steps=1, lr=0.2, bits=8)
    part, test, params0, bf = _setup(fed)
    algs = {n: make_algorithm(n, fed, loss_fn=mlp_loss, template=params0,
                              batch_fn=bf) for n in ("quafl", "fedavg")}
    budget = 40 * 4 * make_codec("lattice", bits=8).message_bits(
        algs["quafl"].d)
    traces = compare(algs, params0, part, jax.random.PRNGKey(2),
                     until_bits=budget, eval_every=0)
    for name, tr in traces.items():
        f = tr.final
        assert f["bits_up_total"] + f["bits_down_total"] >= budget, name
        # per-round schema keys keep their per-round meaning in rows
        assert f["bits_up"] <= f["bits_up_total"], name
    assert traces["quafl"].rounds > 3 * traces["fedavg"].rounds


def test_trace_format_is_uniform():
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2)
    part, test, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    for name in ("quafl", "sequential"):
        alg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                             batch_fn=bf)
        tr = simulate(alg, params0, part, jax.random.PRNGKey(1), rounds=3,
                      eval_every=1,
                      eval_fn=lambda p: {"loss": float(mlp_loss(p, test)[0])})
        assert len(tr.rows) == 3
        for row in tr.rows:
            for k in METRIC_KEYS + ("round", "loss"):
                assert k in row, (name, k)
        # cumulative counters are monotone
        assert tr.column("sim_time") == sorted(tr.column("sim_time"))
        assert tr.column("bits_up_total") == sorted(
            tr.column("bits_up_total"))


def test_unreachable_budget_backstop_still_records_final_row():
    """sequential never sends a bit, so an until_bits budget is
    unreachable: the max_rounds backstop must end the run AND the final
    row (with its eval) must still exist."""
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2)
    part, test, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    alg = make_algorithm("sequential", fed, loss_fn=mlp_loss,
                         template=params0, batch_fn=bf)
    tr = simulate(alg, params0, part, jax.random.PRNGKey(1), until_bits=1e6,
                  eval_every=0, max_rounds=7,
                  eval_fn=lambda p: {"loss": float(mlp_loss(p, test)[0])})
    assert tr.rounds == 7
    assert tr.final["round"] == 7 and "loss" in tr.final


def test_record_every_decouples_metrics_from_eval():
    """record_every traces dense metrics rows; eval_fn only runs on the
    eval cadence (plus the final round)."""
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2)
    part, test, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    n_evals = []
    tr = simulate(alg, params0, part, jax.random.PRNGKey(1), rounds=4,
                  eval_every=0, record_every=1,
                  eval_fn=lambda p: n_evals.append(1) or {"acc": 0.0})
    assert len(tr.rows) == 4 and len(n_evals) == 1   # eval only at done
    assert all("h_zero_frac" in r for r in tr.rows)
    assert [r["round"] for r in tr.rows] == [1, 2, 3, 4]
    assert "acc" in tr.rows[-1] and "acc" not in tr.rows[0]


# ---------------------------------------------------------------------------
# extensions through the registry + harness
# ---------------------------------------------------------------------------

def test_scaffold_through_registry_converges_noniid():
    fed = FedConfig(n_clients=8, s=4, local_steps=4, lr=0.3, bits=10)
    part, test, params0, bf = _setup(fed, iid=False)
    alg = make_algorithm("quafl_scaffold", fed, loss_fn=mlp_loss,
                         template=params0, batch_fn=bf)
    tr = simulate(alg, params0, part, jax.random.PRNGKey(1), rounds=40,
                  eval_every=20,
                  eval_fn=lambda p: {"loss": float(mlp_loss(p, test)[0])})
    assert tr.rows[-1]["loss"] < tr.rows[0]["loss"]
    assert np.isfinite(tr.rows[-1]["c_norm"])


def test_adaptive_walk_stays_in_bounds():
    """AdaptiveBits never leaves [b_min, b_max] — pure controller and the
    registry algorithm driven through simulate()."""
    b_min, b_max, bits = 4, 12, 8
    rng = np.random.default_rng(0)
    for rel in rng.uniform(0, 0.2, size=200):
        bits = AdaptiveBits.walk(bits, float(rel), 0.01, 0.05, b_min, b_max)
        assert b_min <= bits <= b_max

    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.3, bits=12)
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("adaptive_quafl", fed, loss_fn=mlp_loss,
                         template=params0, batch_fn=bf, b_min=4, b_max=12)
    tr = simulate(alg, params0, part, jax.random.PRNGKey(3), rounds=12,
                  eval_every=0)
    trace = tr.final_state.trace
    assert len(trace) == 12
    assert all(4 <= b <= 12 for b in trace)
    # lattice at b=12 has tiny error -> the walk must move DOWN
    assert trace[-1] < 12
