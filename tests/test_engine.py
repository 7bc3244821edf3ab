"""Device-resident round engine: ring buffer, scanned chunks, seed bridge.

Pins the multi_layer_refactor four ways:

  * the device :class:`RingBuffer`'s masked-min pop/push order is
    bit-for-bit the python heap's (``fed.clock.ArrivalQueue``) over
    randomized event streams, ties included,
  * the scanned engine (``simulate(..., scan_chunk=K)``) is bit-for-bit the
    eager loop for every device_round-capable algorithm — params, rows, and
    cumulative bit counters — for quafl, fedavg, fedbuff (device), the
    sequential baseline, and scaffold,
  * the device-resident FedBuff consuming the legacy numpy draws through
    the seed bridge reproduces the python event simulation: identical event
    times/order and float-rounding-level identical model iterates,
  * chunk-boundary budget semantics and the chunked adaptive walk behave as
    documented.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch
from repro.fed import (ArrivalQueue, make_algorithm, ring_init, ring_peek,
                       ring_pop, ring_push, ring_size, simulate,
                       supports_scan)
from repro.fed.engine import RoundEngine, fedbuff_completion_table
from repro.models.mlp import init_mlp_classifier, mlp_loss
from repro.utils.tree import tree_flatten_vector

from hypothesis import given, settings, strategies as st


def _setup(fed, seed=0, iid=True, d=16, hidden=32, classes=4):
    part, test = make_federated_classification(seed, fed.n_clients, d=d,
                                               n_classes=classes, iid=iid)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(seed), d, hidden,
                                     classes)
    bf = lambda dd, k: client_batch(k, dd, d)
    return part, test, params0, bf


# ---------------------------------------------------------------------------
# RingBuffer vs ArrivalQueue: pop/push order pinned bit-for-bit
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_ring_buffer_matches_arrival_queue(seed):
    """Randomized interleaved push/pop streams (duplicate times included to
    exercise the lexicographic (time, client) tie-break): the device
    masked-min pop returns EXACTLY the heap's (t, client) sequence."""
    rng = np.random.default_rng(seed)
    cap = int(rng.integers(2, 9))
    rb, q = ring_init(cap), ArrivalQueue()
    n_live = 0
    for _ in range(60):
        do_push = n_live == 0 or (n_live < cap and rng.random() < 0.6)
        if do_push:
            # float32 times from a small grid so exact ties happen often
            t = np.float32(rng.integers(0, 6) + rng.choice([0.0, 0.5]))
            c = int(rng.integers(0, 5))
            rb = ring_push(rb, t, c)
            q.push(float(t), c)
            n_live += 1
        else:
            tp, cp = ring_peek(rb)
            rb, t, c = ring_pop(rb)
            th, ch = q.pop()
            assert (float(t), int(c)) == (float(th), int(ch))
            assert (float(tp), int(cp)) == (float(th), int(ch))
            n_live -= 1
        assert int(ring_size(rb)) == n_live == len(q)


def test_ring_buffer_ops_trace_under_jit():
    """The queue ops are pure pytree functions: jit-able and scan-able."""
    rb = ring_init(3)
    rb = jax.jit(ring_push)(rb, 2.0, 1)
    rb = jax.jit(ring_push)(rb, 1.0, 2)
    rb, t, c = jax.jit(ring_pop)(rb)
    assert (float(t), int(c)) == (1.0, 2)
    assert int(ring_size(rb)) == 1


# ---------------------------------------------------------------------------
# scanned engine == eager loop, bit-for-bit
# ---------------------------------------------------------------------------

SCAN_NAMES = ("quafl", "fedavg", "fedbuff_device", "sequential",
              "quafl_scaffold")


@pytest.mark.parametrize("name", SCAN_NAMES)
def test_scanned_engine_matches_eager_bitwise(name):
    """rounds=5 with scan_chunk=2 (chunk lengths 2,2,1), dense rows, eval
    cadence 2: final params, every row's schema keys, the eval results, and
    the cumulative bit counters must all be EXACTLY the eager loop's."""
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.3, bits=8)
    part, test, params0, bf = _setup(fed)
    kw = {"buffer_size": 3} if name == "fedbuff_device" else {}
    if name.startswith("quafl"):
        kw = {"uplink": "scalar", "downlink": "scalar"}
    alg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf, **kw)
    assert supports_scan(alg)
    eval_fn = lambda p: {"loss": float(mlp_loss(p, test)[0])}
    run = lambda chunk: simulate(alg, params0, part, jax.random.PRNGKey(3),
                                 rounds=5, eval_every=2, record_every=1,
                                 eval_fn=eval_fn, scan_chunk=chunk)
    tre, trs = run(0), run(2)
    assert tre.engine == "eager" and trs.engine == "scanned"
    fe = np.asarray(tree_flatten_vector(alg.eval_params(tre.final_state)))
    fs = np.asarray(tree_flatten_vector(alg.eval_params(trs.final_state)))
    np.testing.assert_array_equal(fe, fs)
    assert len(tre.rows) == len(trs.rows) == 5
    for re, rs in zip(tre.rows, trs.rows):
        assert re["round"] == rs["round"]
        assert re.get("loss") == rs.get("loss")   # eval rows land identically
        for k in ("sim_time", "round_time", "bits_up", "bits_down",
                  "h_steps_mean", "quant_err", "bits_up_total",
                  "bits_down_total"):
            assert re[k] == rs[k], (name, re["round"], k)


def test_scanned_lattice_quafl_matches_eager():
    """The full rotated-space lattice pipeline under the scanned engine.

    A single-round chunk is bit-identical to the eager round; at chunk
    length >= 2 XLA compiles the loop body with different fusion choices
    than the standalone program and the rotation-heavy kernels accumulate
    <= 1-ulp float32 differences — so multi-round chunks are pinned at
    float32-rounding tolerance (the uncompressed/qsgd paths in
    test_scanned_engine_matches_eager_bitwise stay exact)."""
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.3, bits=8)
    part, test, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    run = lambda chunk, rounds: simulate(
        alg, params0, part, jax.random.PRNGKey(5), rounds=rounds,
        eval_every=0, scan_chunk=chunk)
    # chunk length 1 materializes every round: bit-identical to eager
    np.testing.assert_array_equal(
        np.asarray(run(0, 1).final_state.server),
        np.asarray(run(2, 1).final_state.server))
    tre, trs = run(0, 4), run(4, 4)
    a, b = np.asarray(tre.final_state.server), \
        np.asarray(trs.final_state.server)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-7)


def test_scan_chunk_falls_back_for_host_control_algorithms():
    """python FedBuff has no device_round: scan_chunk must silently run the
    eager engine (and still satisfy the budget semantics)."""
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2)
    part, test, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    alg = make_algorithm("fedbuff", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf, buffer_size=2)
    assert not supports_scan(alg)
    tr = simulate(alg, params0, part, jax.random.PRNGKey(1), rounds=3,
                  eval_every=0, scan_chunk=4)
    assert tr.engine == "eager" and tr.rounds == 3


def test_round_engine_rejects_host_control_algorithms():
    fed = FedConfig(n_clients=4, s=2, local_steps=1)
    part, test, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    alg = make_algorithm("fedbuff", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    with pytest.raises(TypeError):
        RoundEngine(alg)


def test_scan_budget_checked_at_chunk_boundaries():
    """until_sim_time under the scanned engine stops at the first CHUNK
    boundary past the budget — rounds are a multiple of the chunk length
    and the budget is exceeded, never undershot."""
    fed = FedConfig(n_clients=6, s=3, local_steps=1, lr=0.2,
                    codec_up="scalar", codec_down="scalar")
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    # quafl rounds last swt+sit=11s: budget 50s -> eager stops at round 5,
    # scanned (chunks of 4) at the round-8 boundary
    tre = simulate(alg, params0, part, jax.random.PRNGKey(1),
                   until_sim_time=50.0)
    trs = simulate(alg, params0, part, jax.random.PRNGKey(1),
                   until_sim_time=50.0, scan_chunk=4)
    assert tre.rounds == 5 and trs.rounds == 8
    assert trs.final["sim_time"] >= 50.0


def test_adaptive_chunked_walk():
    """The adaptive controller scans via scan_rounds: bits held constant
    inside a chunk, one walk per chunk, trace/bounds preserved."""
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.3, bits=12)
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("adaptive_quafl", fed, loss_fn=mlp_loss,
                         template=params0, batch_fn=bf, b_min=4, b_max=12)
    tr = simulate(alg, params0, part, jax.random.PRNGKey(3), rounds=9,
                  eval_every=0, scan_chunk=3)
    assert tr.engine == "scanned" and tr.rounds == 9
    trace = tr.final_state.trace
    assert len(trace) == 9
    assert all(4 <= b <= 12 for b in trace)
    # within-chunk bits are constant (the walk reacts at boundaries only)
    assert trace[0] == trace[1] == trace[2] == 12
    # lattice at b=12 has tiny error -> the chunk walk must move DOWN
    assert trace[-1] < 12


# ---------------------------------------------------------------------------
# device-resident FedBuff: the seed bridge pins it to the python events
# ---------------------------------------------------------------------------

def test_fedbuff_device_bridge_matches_python_fedbuff():
    """With the completion table replaying the legacy numpy draws, the
    device formulation walks the SAME event sequence as the python heap
    implementation: event times bit-for-bit, bit counters exact, model
    iterates equal to float32 rounding (the python class applies its
    updates op-by-op, the fused round may contract them into FMAs)."""
    fed = FedConfig(n_clients=5, s=3, local_steps=2, lr=0.2)
    part, test, params0, bf = _setup(fed, seed=1)
    key = jax.random.PRNGKey(11)
    rounds, Z = 6, 3
    py = make_algorithm("fedbuff", fed, loss_fn=mlp_loss, template=params0,
                        batch_fn=bf, buffer_size=Z, server_lr=0.5)
    table = fedbuff_completion_table(key, py.lam, fed.local_steps,
                                     n_events=Z * rounds + 2)
    dev = make_algorithm("fedbuff_device", fed, loss_fn=mlp_loss,
                         template=params0, batch_fn=bf, buffer_size=Z,
                         server_lr=0.5, completion_table=table)
    sp, sd = py.init(params0), dev.init(params0)
    for _ in range(rounds):
        sp, mp = py.round(sp, part, key)
        sd, md = dev.round(sd, part, key)
        # same event ORDER and draws through the bridge; the device clock
        # accumulates event times in float32 (python sums in float64)
        np.testing.assert_allclose(float(md["sim_time"]),
                                   float(mp["sim_time"]), rtol=1e-6)
        assert float(mp["bits_up"]) == float(md["bits_up"])
        assert float(mp["bits_down"]) == float(md["bits_down"])
    np.testing.assert_allclose(np.asarray(sp.server), np.asarray(sd.server),
                               rtol=1e-5, atol=1e-6)


def test_fedbuff_device_quantized_roundtrip():
    """Quantized deltas ride the device round too (scalar + lattice), with
    a finite quant_err metric and the legacy per-flush bit accounting."""
    for uplink in ("scalar", "lattice"):
        fed = FedConfig(n_clients=4, s=2, local_steps=1, bits=8)
        part, test, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
        alg = make_algorithm("fedbuff_device", fed, loss_fn=mlp_loss,
                             template=params0, batch_fn=bf, buffer_size=2,
                             uplink=uplink)
        st1, m = alg.round(alg.init(params0), part, jax.random.PRNGKey(2))
        assert float(m["bits_up"]) == 2 * alg.codec_up.message_bits(alg.d)
        assert float(m["bits_down"]) == 2 * alg.d * 32
        assert np.isfinite(float(m["quant_err"]))
        assert float(m["quant_err"]) > 0.0
        assert np.all(np.isfinite(np.asarray(st1.server)))


def test_fedbuff_device_exhausted_bridge_table_is_loud():
    """Simulating past the bridge table's replayed events must poison the
    clock with NaN (a silently clamped gather would quietly de-pin the
    event stream from the legacy draws)."""
    fed = FedConfig(n_clients=3, s=2, local_steps=1, lr=0.2)
    part, test, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    key = jax.random.PRNGKey(0)
    lam = np.full(3, fed.lam_fast, np.float32)
    table = fedbuff_completion_table(key, lam, fed.local_steps, n_events=1)
    alg = make_algorithm("fedbuff_device", fed, loss_fn=mlp_loss,
                         template=params0, batch_fn=bf, buffer_size=2,
                         completion_table=table)
    st = alg.init(params0)
    for _ in range(4):   # 8 completions >> the 1 replayed redraw
        st, m = alg.round(st, part, key)
    assert np.isnan(float(st.sim_time))


def test_fedbuff_device_unseeded_draws_are_deterministic():
    """Without a bridge table the durations come from the device stream:
    same init + same round keys -> identical trajectories."""
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2)
    part, test, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    alg = make_algorithm("fedbuff_device", fed, loss_fn=mlp_loss,
                         template=params0, batch_fn=bf, buffer_size=2)
    runs = []
    for _ in range(2):
        st = alg.init(params0)
        for r in range(3):
            st, m = alg.round(st, part, jax.random.PRNGKey(4))
        runs.append((np.asarray(st.server), float(st.sim_time)))
    np.testing.assert_array_equal(runs[0][0], runs[1][0])
    assert runs[0][1] == runs[1][1]


# ---------------------------------------------------------------------------
# spmd through the registry + simulate()
# ---------------------------------------------------------------------------

def test_spmd_registry_simulates_with_standard_schema():
    """--algo spmd semantics: the mesh train step behind the protocol emits
    standardized Trace rows through simulate(), and the scanned engine
    reproduces the eager run bit-for-bit."""
    from repro.configs import get_reduced
    from repro.data.synthetic import federated_token_task
    from repro.fed.api import METRIC_KEYS

    cfg = get_reduced("llama3.2-1b")
    fed = FedConfig(n_clients=1, s=1, local_steps=2, lr=0.05, bits=8)
    from repro.models.model import init_lm
    params0, _ = init_lm(cfg, jax.random.PRNGKey(0))
    data, bf = federated_token_task(0, 1, 8, 2, 16, cfg.vocab_size)
    alg = make_algorithm("spmd", fed, loss_fn=None, template=params0,
                         batch_fn=bf, cfg=cfg, batch=2, seq=16)
    run = lambda chunk: simulate(alg, params0, data, jax.random.PRNGKey(1),
                                 rounds=2, eval_every=0, record_every=1,
                                 scan_chunk=chunk)
    tre, trs = run(0), run(2)
    for row in tre.rows:
        for k in METRIC_KEYS:
            assert k in row and np.isfinite(row[k]), (k, row)
        assert row["bits_up"] > 0 and row["quant_err"] > 0
    assert tre.rows[1]["sim_time"] == 2 * (fed.swt + fed.sit)
    pe, ps = tre.final_state.train.server, trs.final_state.train.server
    for k in pe:
        np.testing.assert_array_equal(np.asarray(pe[k]), np.asarray(ps[k]))


def test_spmd_requires_model_config():
    fed = FedConfig(n_clients=2, s=2, local_steps=1)
    with pytest.raises(ValueError):
        make_algorithm("spmd", fed, loss_fn=None, template={},
                       batch_fn=None)
