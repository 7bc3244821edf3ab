"""Population engine: sharded client-state store + Participation specs.

Pins the new_subsystem four ways:

  * the gather/scatter population path is bit-for-bit the pre-refactor
    dense path at small N — the spec-resolved ``uniform`` run reproduces
    the PR 3 golden anchor (``tests/golden_pr3.npz``) for quafl, fedavg,
    quafl_scaffold, and fedbuff_device, server vectors and bit counters,
  * participation schedules are pure functions of ``(key, t, n, s)``: the
    cyclic spec is deterministic across ``lax.scan`` chunk boundaries
    (eager == scanned bitwise, chunks straddling phase flips included),
  * per-client RNG derives lazily from ``(base_key, client_id)``: draws are
    stable under sample reordering AND under resharding the store over an
    8-device client mesh (subprocess),
  * N is a spec, not a hot-path cost: the ``perf_smoke`` gate runs the
    scanned engine at N=10^3 and N=10^5 (fixed s=8) and asserts the
    us_per_round stays flat (Floyd's sampler — no O(N log N) permutation).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch
from repro.fed import (CyclicParticipation, GammaStragglerParticipation,
                       UniformParticipation, build_population, client_keys,
                       floyd_sample, gather_rows, make_algorithm,
                       register_participation, registered_participations,
                       resolve_participation, sample_clients, scatter_rows,
                       simulate, uniform_sample, with_rows)
from repro.fed.population import DENSE_SAMPLE_MAX, lazy_h_steps_per_client
from repro.models.mlp import init_mlp_classifier, mlp_loss
from repro.utils.tree import tree_flatten_vector

from hypothesis import given, settings, strategies as st


GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden_pr3.npz")


def _setup(fed, seed=0, d=16, hidden=32, classes=4):
    part, test = make_federated_classification(seed, fed.n_clients, d=d,
                                               n_classes=classes)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(seed), d, hidden,
                                     classes)
    bf = lambda dd, k: client_batch(k, dd, d)
    return part, test, params0, bf


# ---------------------------------------------------------------------------
# the store: build / gather / scatter / rows
# ---------------------------------------------------------------------------

def test_build_population_speed_groups():
    fed = FedConfig(n_clients=10, s=2, slow_frac=0.3)
    pop = build_population(fed)
    assert pop.n == 10
    lam, group = np.asarray(pop.row("lam")), np.asarray(pop.row("group"))
    # the clock's split: first slow_frac*n clients are slow (group label 1)
    assert group.sum() == 3 and group[:3].all()
    np.testing.assert_array_equal(lam[:3], fed.lam_slow)
    np.testing.assert_array_equal(lam[3:], fed.lam_fast)


def test_gather_scatter_roundtrip():
    fed = FedConfig(n_clients=8, s=3)
    pop = build_population(fed, model=jnp.arange(8 * 4, dtype=jnp.float32)
                           .reshape(8, 4))
    idx = jnp.asarray([6, 1, 4])
    got = gather_rows(pop, idx)
    np.testing.assert_array_equal(np.asarray(got["lam"]),
                                  np.asarray(pop.row("lam"))[[6, 1, 4]])
    pop2 = scatter_rows(pop, idx, {"model": got["model"] + 100.0})
    m2 = np.asarray(pop2.row("model"))
    m0 = np.asarray(pop.row("model"))
    np.testing.assert_array_equal(m2[[6, 1, 4]], m0[[6, 1, 4]] + 100.0)
    untouched = [i for i in range(8) if i not in (6, 1, 4)]
    np.testing.assert_array_equal(m2[untouched], m0[untouched])
    # rows not named in the scatter are carried through BY REFERENCE
    assert pop2.row("lam") is pop.row("lam")
    # with_rows adds without copying existing rows
    pop3 = with_rows(pop, extra=jnp.zeros((8,)))
    assert pop3.row("model") is pop.row("model") and pop3.n == 8


# ---------------------------------------------------------------------------
# samplers: legacy pin below the threshold, Floyd above it
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_uniform_sample_pins_legacy_draw_at_small_n(seed):
    """Below DENSE_SAMPLE_MAX the uniform sampler IS clock.sample_clients
    bit-for-bit — the golden anchors (and every existing seeded run) live
    on this branch."""
    key = jax.random.PRNGKey(seed)
    np.testing.assert_array_equal(np.asarray(uniform_sample(key, 64, 5)),
                                  np.asarray(sample_clients(key, 64, 5)))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_floyd_sample_is_valid_subset(seed):
    n, s = 50_000, 8
    ids = np.asarray(floyd_sample(jax.random.PRNGKey(seed), n, s))
    assert len(set(ids.tolist())) == s          # without replacement
    assert (ids >= 0).all() and (ids < n).all()


def test_uniform_sample_switches_to_floyd_above_threshold():
    key, n, s = jax.random.PRNGKey(3), DENSE_SAMPLE_MAX + 1, 6
    np.testing.assert_array_equal(np.asarray(uniform_sample(key, n, s)),
                                  np.asarray(floyd_sample(key, n, s)))


def test_floyd_sample_is_roughly_uniform():
    """Every client must be reachable with about equal frequency — Floyd's
    duplicate->j redirect must not visibly bias the tail indices."""
    n, s, rounds = 40, 5, 2000
    counts = np.zeros(n)
    for r in range(rounds):
        ids = np.asarray(floyd_sample(jax.random.PRNGKey(r), n, s))
        counts[ids] += 1
    expect = rounds * s / n                      # 250 per client
    assert counts.min() > 0.7 * expect and counts.max() < 1.3 * expect


# ---------------------------------------------------------------------------
# participation specs: semantics, registry, grammar
# ---------------------------------------------------------------------------

def test_resolve_participation_precedence():
    inst = CyclicParticipation(period=4, phase_groups=2)
    assert resolve_participation(inst) is inst
    assert isinstance(resolve_participation("uniform"),
                      UniformParticipation)
    fed = FedConfig(n_clients=8, s=2,
                    participation="gamma_straggler:strength=2")
    p = resolve_participation(None, fed)
    assert isinstance(p, GammaStragglerParticipation) and p.strength == 2
    # explicit spec overrides the config default
    assert isinstance(resolve_participation("uniform", fed),
                      UniformParticipation)
    assert isinstance(resolve_participation(None, None),
                      UniformParticipation)


def test_participation_spec_grammar_errors():
    with pytest.raises(ValueError, match="unknown participation"):
        resolve_participation("diurnal")
    with pytest.raises(ValueError, match="malformed"):
        resolve_participation("cyclic:period8")
    with pytest.raises(TypeError):
        resolve_participation(42)
    with pytest.raises(ValueError, match="period"):
        CyclicParticipation(period=3, phase_groups=2)
    with pytest.raises(ValueError, match="period >= phase_groups"):
        CyclicParticipation(period=2, phase_groups=4)


def test_participation_registry_extensible_and_loud_on_duplicates():
    names = registered_participations()
    assert {"uniform", "gamma_straggler", "cyclic"} <= set(names)
    with pytest.raises(ValueError, match="already registered"):
        register_participation("uniform", UniformParticipation)
    register_participation("test_everyone_0",
                           lambda **kw: UniformParticipation())
    try:
        assert isinstance(resolve_participation("test_everyone_0"),
                          UniformParticipation)
    finally:
        from repro.fed.population import _PARTICIPATIONS
        _PARTICIPATIONS.pop("test_everyone_0", None)


def test_cyclic_sample_stays_in_active_phase_group():
    p = CyclicParticipation(period=8, phase_groups=4)   # 2 rounds per phase
    n, s, m = 20, 3, 5
    for t in range(16):
        g = int(p.group_at(t))
        assert g == (t // 2) % 4
        ids = np.asarray(p.sample(jax.random.PRNGKey(t), t, n, s))
        assert len(set(ids.tolist())) == s
        assert (ids >= g * m).all() and (ids < (g + 1) * m).all()


def test_cyclic_validates_population_shape_at_trace_time():
    p = CyclicParticipation(period=4, phase_groups=2)
    with pytest.raises(ValueError, match="divisible"):
        p.sample(jax.random.PRNGKey(0), 0, 9, 2)
    with pytest.raises(ValueError, match="exceeds"):
        p.sample(jax.random.PRNGKey(0), 0, 8, 5)


def test_gamma_straggler_prefers_fast_clients():
    """Availability ∝ λ^strength: the fast 70% must answer polls far more
    often per client than the slow 30% (λ_fast/λ_slow = 4 here)."""
    fed = FedConfig(n_clients=50, s=5, slow_frac=0.4)
    pop = build_population(fed)
    lam = pop.row("lam")
    p = GammaStragglerParticipation(strength=2.0)
    counts = np.zeros(50)
    for r in range(400):
        ids = np.asarray(p.sample(jax.random.PRNGKey(r), r, 50, 5, lam))
        assert len(set(ids.tolist())) == 5
        counts[ids] += 1
    slow = counts[:20].mean()
    fast = counts[20:].mean()
    assert fast > 3.0 * slow, (slow, fast)
    with pytest.raises(ValueError, match="lam"):
        p.sample(jax.random.PRNGKey(0), 0, 50, 5, None)


# ---------------------------------------------------------------------------
# lazy per-client RNG: identity-keyed, order- and sharding-invariant
# ---------------------------------------------------------------------------

def test_client_keys_are_identity_keyed():
    base = jax.random.PRNGKey(9)
    a = np.asarray(client_keys(base, jnp.asarray([5, 1, 9])))
    b = np.asarray(client_keys(base, jnp.asarray([9, 5, 1])))
    np.testing.assert_array_equal(a[0], b[1])
    np.testing.assert_array_equal(a[1], b[2])
    np.testing.assert_array_equal(a[2], b[0])
    # and equal to the scalar derivation
    np.testing.assert_array_equal(
        a[0], np.asarray(jax.random.fold_in(base, 5)))


def test_lazy_h_steps_per_client_stable_under_reordering():
    base = jax.random.PRNGKey(4)
    lam = jnp.asarray([0.5, 0.125, 0.5, 0.125], jnp.float32)
    elapsed = jnp.asarray([10.0, 20.0, 30.0, 40.0], jnp.float32)
    ids = jnp.asarray([3, 0, 2, 1])
    h1 = np.asarray(lazy_h_steps_per_client(base, ids, lam[ids],
                                            elapsed[ids], 10))
    perm = jnp.asarray([1, 3, 0, 2])    # same clients, different order
    h2 = np.asarray(lazy_h_steps_per_client(base, ids[perm], lam[ids][perm],
                                            elapsed[ids][perm], 10))
    np.testing.assert_array_equal(h1[np.asarray(perm)], h2)
    assert (h1 <= 10).all() and (h1 >= 0).all()


# ---------------------------------------------------------------------------
# population path == dense path: the PR 3 golden anchor through the specs
# ---------------------------------------------------------------------------

GOLDEN_ALGS = {
    "quafl": {},
    "quafl_scaffold": {},
    "fedavg": {},
    "fedbuff_device": dict(buffer_size=2, uplink="lattice"),
}


@pytest.mark.parametrize("name", sorted(GOLDEN_ALGS))
def test_population_path_matches_pr3_golden(name):
    """The store-backed gather/scatter round (with the participation spec
    resolved EXPLICITLY, not defaulted) reproduces the pre-population
    golden slice bit-for-bit: server vector and per-round bit counters."""
    golden = np.load(GOLDEN_PATH)
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.3, bits=8)
    part, _, params0, bf = _setup(fed)
    kw = dict(GOLDEN_ALGS[name])
    if name != "fedbuff_device":    # event-driven: no per-round draw
        kw["participation"] = UniformParticipation()
    alg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf, **kw)
    state = alg.init(params0)
    key = jax.random.PRNGKey(7)
    ups, downs = [], []
    for _ in range(3):
        key, sub = jax.random.split(key)
        state, m = alg.round(state, part, sub)
        ups.append(float(m["bits_up"]))
        downs.append(float(m["bits_down"]))
    np.testing.assert_array_equal(
        np.asarray(tree_flatten_vector(alg.eval_params(state))),
        golden[f"{name}/server"])
    np.testing.assert_array_equal(np.asarray(ups), golden[f"{name}/bits_up"])
    np.testing.assert_array_equal(np.asarray(downs),
                                  golden[f"{name}/bits_down"])


def test_population_larger_than_cohort_trains():
    """n_clients > s through every sampling algorithm: the store holds n
    rows, the round exchanges s messages (bits accounting unchanged)."""
    fed = FedConfig(n_clients=24, s=4, local_steps=2, lr=0.3, bits=8)
    part, test, params0, bf = _setup(fed)
    for name in ("quafl", "fedavg", "quafl_scaffold"):
        kw = ({"uplink": "scalar", "downlink": "scalar"}
              if name.startswith("quafl") else {})
        alg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                             batch_fn=bf,
                             participation="gamma_straggler:strength=1",
                             **kw)
        tr = simulate(alg, params0, part, jax.random.PRNGKey(2), rounds=4,
                      eval_every=0)
        v = np.asarray(tree_flatten_vector(alg.eval_params(tr.final_state)))
        assert np.isfinite(v).all(), name
        assert tr.final["bits_up"] > 0


# ---------------------------------------------------------------------------
# cyclic determinism across scan chunk boundaries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ("quafl", "fedavg"))
def test_cyclic_schedule_deterministic_across_chunk_boundaries(name):
    """8 rounds of cyclic:period=4,phase_groups=2 under scan_chunk=3
    (chunks 3,3,2 — every chunk straddles a phase flip): the scanned run
    must be bit-for-bit the eager run, because the schedule is a pure
    function of the round counter t carried in the state."""
    fed = FedConfig(n_clients=8, s=2, local_steps=2, lr=0.3,
                    participation="cyclic:period=4,phase_groups=2")
    part, test, params0, bf = _setup(fed)
    kw = ({"uplink": "scalar", "downlink": "scalar"} if name == "quafl"
          else {})
    alg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf, **kw)
    run = lambda chunk: simulate(alg, params0, part, jax.random.PRNGKey(5),
                                 rounds=8, eval_every=0, record_every=1,
                                 scan_chunk=chunk)
    tre, trs = run(0), run(3)
    assert tre.engine == "eager" and trs.engine == "scanned"
    fe = np.asarray(tree_flatten_vector(alg.eval_params(tre.final_state)))
    fs = np.asarray(tree_flatten_vector(alg.eval_params(trs.final_state)))
    np.testing.assert_array_equal(fe, fs)
    for re, rs in zip(tre.rows, trs.rows):
        for k in ("sim_time", "bits_up_total", "h_steps_mean"):
            assert re[k] == rs[k], (re["round"], k)


def test_cyclic_last_time_rows_respect_schedule():
    """Only the active phase group's clients interact: after the first
    phase (2 rounds of group 0) no group-1 client may have a last_time
    update yet, and over a full period every group gets touched."""
    fed = FedConfig(n_clients=8, s=4, local_steps=1, lr=0.1,
                    codec_up="scalar", codec_down="scalar",
                    participation="cyclic:period=2,phase_groups=2")
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    state = alg.init(params0)
    state, _ = alg.round(state, part, jax.random.PRNGKey(0))
    lt = np.asarray(state.last_time)
    assert (lt[:4] > 0).all() and (lt[4:] == 0).all()   # s=4 = group size
    state, _ = alg.round(state, part, jax.random.PRNGKey(1))
    lt = np.asarray(state.last_time)
    assert (lt > 0).all()


# ---------------------------------------------------------------------------
# resharding: an 8-device client mesh must not change ANY draw or iterate
# ---------------------------------------------------------------------------

SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from repro.configs.base import FedConfig
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch
from repro.fed import (build_population, client_keys, client_mesh,
                       make_algorithm, shard_population, simulate)
from repro.fed.population import lazy_h_steps_per_client
from repro.models.mlp import init_mlp_classifier, mlp_loss
from repro.utils.tree import tree_flatten_vector

assert jax.device_count() == 8
mesh = client_mesh()
fed = FedConfig(n_clients=16, s=4, local_steps=2, lr=0.3,
                codec_up="scalar", codec_down="scalar",
                participation="gamma_straggler:strength=1")

# 1) sharding moves placement, never values
pop = build_population(fed, model=jnp.arange(16 * 4, dtype=jnp.float32)
                       .reshape(16, 4))
sh = shard_population(pop, mesh)
for name in pop.rows:
    np.testing.assert_array_equal(np.asarray(sh.rows[name]),
                                  np.asarray(pop.rows[name]))
assert len(set(d.device for d in sh.rows["model"].addressable_shards)) == 8

# 2) per-client draws are identity-keyed: identical from sharded and
#    unsharded lam rows
base = jax.random.PRNGKey(3)
ids = jnp.asarray([13, 2, 7, 11])
h_dense = lazy_h_steps_per_client(base, ids, pop.rows["lam"][ids],
                                  jnp.full((4,), 12.0), 10)
h_shard = lazy_h_steps_per_client(base, ids, sh.rows["lam"][ids],
                                  jnp.full((4,), 12.0), 10)
np.testing.assert_array_equal(np.asarray(h_dense), np.asarray(h_shard))
np.testing.assert_array_equal(np.asarray(client_keys(base, ids)),
                              np.asarray(jnp.stack(
                                  [jax.random.fold_in(base, int(i))
                                   for i in ids])))

# 3) a full run with the store sharded over the client mesh is bit-for-bit
#    the unsharded run (gamma participation exercises per-client keys)
part, _ = make_federated_classification(0, 16, d=16, n_classes=4)
params0, _ = init_mlp_classifier(jax.random.PRNGKey(0), 16, 32, 4)
bf = lambda dd, k: client_batch(k, dd, 16)
servers = {}
for label, cm in (("dense", None), ("sharded", mesh)):
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf, client_mesh=cm)
    tr = simulate(alg, params0, part, jax.random.PRNGKey(5), rounds=4,
                  eval_every=0)
    servers[label] = np.asarray(
        tree_flatten_vector(alg.eval_params(tr.final_state)))
np.testing.assert_array_equal(servers["dense"], servers["sharded"])
print("POP_SUBPROC_OK")
"""


def test_rng_and_rounds_stable_under_resharding_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SUBPROC], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "POP_SUBPROC_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# scan_chunk="auto": the tuned run equals the explicit run bitwise
# ---------------------------------------------------------------------------

def test_auto_chunk_matches_explicit_bitwise():
    fed = FedConfig(n_clients=8, s=3, local_steps=2, lr=0.3,
                    codec_up="scalar", codec_down="scalar")
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    tra = simulate(alg, params0, part, jax.random.PRNGKey(2), rounds=12,
                   eval_every=0, record_every=1, scan_chunk="auto")
    assert tra.engine == "scanned" and tra.scan_chunk >= 2
    assert alg._round_engine.tuned_chunk == tra.scan_chunk   # cached
    trk = simulate(alg, params0, part, jax.random.PRNGKey(2), rounds=12,
                   eval_every=0, record_every=1,
                   scan_chunk=tra.scan_chunk)
    fa = np.asarray(tree_flatten_vector(alg.eval_params(tra.final_state)))
    fk = np.asarray(tree_flatten_vector(alg.eval_params(trk.final_state)))
    np.testing.assert_array_equal(fa, fk)
    assert [r["sim_time"] for r in tra.rows] == \
        [r["sim_time"] for r in trk.rows]


def test_auto_chunk_capped_by_eval_cadence():
    """Autotune must never pick a chunk longer than the eval cadence —
    evals only fire on chunk boundaries."""
    fed = FedConfig(n_clients=6, s=2, local_steps=1, lr=0.2)
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("fedavg", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    eval_fn = lambda p: {"loss": float(mlp_loss(p, test)[0])}
    tr = simulate(alg, params0, part, jax.random.PRNGKey(1), rounds=9,
                  eval_every=3, eval_fn=eval_fn, scan_chunk="auto")
    assert 2 <= tr.scan_chunk <= 3
    assert [r["round"] for r in tr.rows] == [3, 6, 9]
    assert all("loss" in r for r in tr.rows)


def test_auto_chunk_falls_back_eager_for_host_algorithms():
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2)
    part, test, params0, bf = _setup(fed)
    alg = make_algorithm("fedbuff", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf, buffer_size=2)
    tr = simulate(alg, params0, part, jax.random.PRNGKey(1), rounds=3,
                  eval_every=0, scan_chunk="auto")
    assert tr.engine == "eager" and tr.scan_chunk == 0


# ---------------------------------------------------------------------------
# perf gate: N is memory, not per-round time
# ---------------------------------------------------------------------------

def _flat_alg(n_clients: int, d: int = 256):
    fed = FedConfig(n_clients=n_clients, s=8, local_steps=2, lr=0.01,
                    codec_up="identity", codec_down="identity")
    key = jax.random.PRNGKey(0)
    params0 = {"w": 0.01 * jax.random.normal(key, (d,), jnp.float32)}
    data = {"c": jnp.ones((1, 4), jnp.float32)}   # shared tiny batch pool

    def loss_fn(params, batch):
        w = params["w"]
        return 0.5 * jnp.mean(batch["c"]) * jnp.sum(w * w), {}

    def bf(client_data, k):
        return {"c": client_data["c"]}

    alg = make_algorithm("quafl", fed, loss_fn=loss_fn, template=params0,
                         batch_fn=bf)
    return alg, params0, data


@pytest.mark.perf_smoke
def test_perf_smoke_round_cost_independent_of_population_size():
    """The population engine's contract: us_per_round at N=10^5 within
    1.5x of N=10^3 (fixed s=8, scanned engine) — the uniform sampler must
    be on Floyd's O(s^2) branch, the state updates on the O(s·d)
    gather/scatter, with no hidden O(N) per-round work besides the O(N)
    carry XLA keeps resident."""
    us = {}
    for n in (1_000, 100_000):
        alg, params0, data = _flat_alg(n)
        for _ in range(2):   # compile+warmup, then the timed run
            tr = simulate(alg, params0, data, jax.random.PRNGKey(3),
                          rounds=40, eval_every=0, scan_chunk=10)
        assert tr.engine == "scanned"
        us[n] = tr.us_per_round
    # generous floor so sub-ms timing jitter can't fail a healthy run
    base = max(us[1_000], 200.0)
    assert us[100_000] < 1.5 * base, us
