"""Gate tests for ``repro.analysis``: the real matrix is clean, and every
analyzer provably fires on a mutation fixture.

The clean half runs the SAME checks ``python -m repro.analysis.lint``
runs (jaxpr invariants for every registry algorithm × codec, rotation
op-budget, donation audit, recompile sentinel, AST rules over src/repro),
at the tiny lint config. The mutation half hand-builds a violating
program per rule — key reuse with distinct derivations, a host callback
in a traced body, a donated-but-unaliasable buffer, an f64 leak, a
mid-run retrace — and asserts the matching analyzer reports it.
"""
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.astlint import lint_source
from repro.analysis.donation import audit_lowered
from repro.analysis.jaxpr import (analyze_jaxpr, check_host_callbacks,
                                  check_key_discipline, check_wide_dtypes,
                                  op_counts)
from repro.analysis.lint import (MATRIX_CODECS, _build_cell, _cells,
                                 _traceable, analyze_cell, sentinel_run)
from repro.analysis.opbudget import (OpBudget, check_rotation_budget,
                                     rotation_budget)
from repro.analysis.sentinel import RecompileSentinel

# ---------------------------------------------------------------------------
# the real matrix is clean
# ---------------------------------------------------------------------------

# every registry algorithm (minus the python event-driven fedbuff) × codec
ALL_CELLS = sorted(set(_cells()))


@pytest.mark.parametrize("alg_name,codec",
                         ALL_CELLS, ids=[f"{a}x{c}" for a, c in ALL_CELLS])
def test_matrix_cell_trace_clean(alg_name, codec):
    """Host-callback / wide-dtype / key-discipline / op-budget checks pass
    on the traced round and scanned chunk of every real cell. Donation
    (a compile per cell) is covered on a subset below."""
    rep = analyze_cell(alg_name, codec, donation=False)
    assert rep["violations"] == [], rep["violations"]


@pytest.mark.parametrize("alg_name", ["quafl", "fedavg"])
def test_donation_audit_clean(alg_name):
    """The engine's scanned chunk donates every state leaf and XLA honors
    every donation (checked against the compiled executable's
    input_output_alias table)."""
    rep = analyze_cell(alg_name, "lattice", donation=True)
    assert rep["violations"] == [], rep["violations"]
    d = rep["donation"]
    assert d["donation_intent"] == d["state_leaves"]
    assert d["aliased"] == d["donation_intent"]


def test_sentinel_one_compile_per_chunk_length():
    """A scanned simulate() run compiles each chunk program exactly once —
    the recompile sentinel interrogates the engine's jit cache."""
    rep = sentinel_run("quafl")
    assert rep["violations"] == [], rep["violations"]
    assert rep["compiles"] == {"chunk2": 1}


def test_rotation_budget_via_opbudget_api():
    """The promoted op-budget audit reproduces the pipeline invariant:
    s+1 forward / s+1 inverse rotation passes per QuAFL round."""
    alg, data, params0, key = _build_cell("quafl", "lattice")
    state = alg.init(params0)
    assert check_rotation_budget(alg, state, data, key, "quafl") == []
    # and a wrong budget is reported, proving the check is live
    bad = check_rotation_budget(alg, state, data, key, "quafl",
                                budget={"rotation_fwd": 99})
    assert [v.rule for v in bad] == ["op-budget"]


def test_opbudget_legacy_surface():
    b = OpBudget()
    b.fwd += 3
    b.inv += 3
    assert b.counters == {"rotation_fwd": 3, "rotation_inv": 3}
    assert b.expect("x", rotation_budget(2)) == []   # s=2 -> 3 fwd / 3 inv
    b.reset()
    assert b.fwd == 0 and b.counters == {}


def test_ast_lint_clean_on_repo():
    import os
    from repro.analysis import astlint
    # src/repro (repro may be a namespace package without __file__)
    root = os.path.dirname(os.path.dirname(os.path.abspath(
        astlint.__file__)))
    viols = astlint.lint_path(root)
    assert viols == [], [v.as_dict() for v in viols]


# ---------------------------------------------------------------------------
# mutation fixtures: each analyzer provably fires
# ---------------------------------------------------------------------------

def test_mutation_key_reuse_detected():
    """One key consumed by two DISTINCT derivations is the schedule-
    corrupting bug; the same derivation twice (shared-dither idiom) and
    fold_in domain separation stay legal."""
    def bad(key):
        return jax.random.uniform(key, (8,)) + jax.random.normal(key, (4,)).sum()

    viols = check_key_discipline(jax.make_jaxpr(bad)(jax.random.PRNGKey(0)),
                                 "fixture")
    assert [v.rule for v in viols] == ["key-reuse"]

    def shared_dither(key):   # same derivation twice: legal by design
        return jax.random.uniform(key, (8,)) + jax.random.uniform(key, (8,))

    assert check_key_discipline(
        jax.make_jaxpr(shared_dither)(jax.random.PRNGKey(0)), "ok") == []

    def folded(key):          # fold_in is the canonical fix: legal
        return (jax.random.uniform(jax.random.fold_in(key, 1), (8,)).sum()
                + jax.random.normal(jax.random.fold_in(key, 2), (4,)).sum())

    assert check_key_discipline(
        jax.make_jaxpr(folded)(jax.random.PRNGKey(0)), "ok") == []


def test_mutation_key_reuse_across_scan_detected():
    """Reuse hiding across a scan boundary (key drawn outside AND consumed
    differently inside the body) is still caught."""
    def bad(key):
        x = jax.random.uniform(key, (8,))

        def body(c, _):
            # a DIFFERENT derivation ((4,) draw) of the key the outer
            # uniform already consumed with an (8,) draw
            return c + jax.random.normal(key, (4,)).sum(), None

        y, _ = jax.lax.scan(body, x, None, length=3)
        return y

    viols = check_key_discipline(jax.make_jaxpr(bad)(jax.random.PRNGKey(0)),
                                 "fixture")
    assert any(v.rule == "key-reuse" for v in viols)


def test_mutation_host_callback_detected():
    def bad(x):
        jax.debug.print("x = {}", x)
        return x * 2

    viols = check_host_callbacks(jax.make_jaxpr(bad)(jnp.ones(3)), "fixture")
    assert [v.rule for v in viols] == ["host-callback"]
    assert check_host_callbacks(
        jax.make_jaxpr(lambda x: x * 2)(jnp.ones(3)), "ok") == []


def test_mutation_f64_leak_detected():
    with jax.enable_x64(True):
        def bad(x):
            return x.astype(jnp.float64) * 2.0

        closed = jax.make_jaxpr(bad)(jnp.ones(3, jnp.float32))
    viols = check_wide_dtypes(closed, "fixture")
    assert [v.rule for v in viols] == ["wide-dtype"]


def test_mutation_donation_miss_detected():
    """A donated buffer no output can alias is a silent copy; the audit
    reports the dropped intent."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # donated x is unused: jit records no donation intent for it
        f = jax.jit(lambda x, y: y * 2, donate_argnums=(0,))
        lowered = f.lower(jnp.ones(4), jnp.ones(3))
        viols = audit_lowered(lowered, 1, "fixture")
    assert "donation" in viols[0].rule
    # the clean case: donated input aliased 1:1 into the output
    g = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    assert audit_lowered(g.lower(jnp.ones(4)), 1, "ok") == []


def test_mutation_recompile_detected():
    """Sentinel trips on (a) a traced program changing under one tag and
    (b) a jit cache holding two compilations of one chunk program."""
    s = RecompileSentinel()
    s.record("tag", jax.make_jaxpr(lambda x: x + 1)(jnp.ones(3)))
    s.record("tag", jax.make_jaxpr(lambda x: x * 2)(jnp.ones(3)))
    assert [v.rule for v in s.report()] == ["recompile"]

    class FakeEngine:
        _chunk_fns = {2: jax.jit(lambda s, d, k: s)}

    # two different input shapes -> two compilations in the cache
    FakeEngine._chunk_fns[2](jnp.ones(3), 0, 0)
    FakeEngine._chunk_fns[2](jnp.ones(4), 0, 0)
    viols = RecompileSentinel().check_engine("tag", FakeEngine())
    assert [v.rule for v in viols] == ["recompile"]


def test_mutation_op_budget_blown_detected():
    b = OpBudget()
    b.add("rotation_fwd", 5)
    b.add("rotation_inv", 3)
    viols = b.expect("fixture", rotation_budget(2))
    # fwd 5 != budgeted 3 is reported; inv 3 == 3 is clean
    assert [v.rule for v in viols] == ["op-budget"]
    assert "rotation_fwd" in viols[0].detail


def test_analyze_jaxpr_reports_tracked_ops():
    def f(x):
        return x.astype(jnp.int32).astype(jnp.float32)

    viols, rep = analyze_jaxpr(jax.make_jaxpr(f)(jnp.ones(3)), "x")
    assert viols == []
    assert rep["convert_element_type"] == 2
    assert rep["eqns_total"] >= 2
    assert op_counts(jax.make_jaxpr(f)(jnp.ones(3)))["convert_element_type"] == 2


def test_rs_transport_audit_clean_and_byte_gate_trips():
    """The fused shard_local_rs exchange, traced on an abstract (4, 2)
    mesh, moves integer codes + scalar γ rows over its all-gather and only
    scalar hints over psum — and the byte budget FAILS the fixture where
    the fp32 aggregate rides the wire instead."""
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.analysis.lint import rs_transport_audit
    from repro.analysis.opbudget import check_collective_bytes
    from repro.compression.codecs import resolve_codec
    from repro.compression.transports import transport_for_mode
    from repro.configs.base import FedConfig
    from repro.core.exchange_local import make_shardlocal_exchange

    rep = rs_transport_audit()
    assert rep["violations"] == []
    ops = rep["ops"]
    # the reducing phase is the ONE fp32-sized collective; the re-gather
    # is coded (ints) with a scalars-only float side channel
    assert ops["reduce_scatter_fbytes"] == (1 << 16) * 4
    assert 0 < ops["all_gather_ibytes"] <= (1 << 16)
    assert ops["all_gather_fbytes"] <= 64 * 4
    assert ops["psum_fbytes"] <= 4096

    # regression fixture: fp32 psum transport under the same budget
    n, d = 4, 1 << 16
    mesh = AbstractMesh((n, 2), ("data", "model"))
    fed = FedConfig(n_clients=n, s=n, bits=8,
                    codec_up="lattice_packed:bits=4",
                    codec_down="lattice_packed:bits=4")
    up = resolve_codec(None, fed, direction="up", default="lattice")
    dn = resolve_codec(None, fed, direction="down", default="lattice")
    ex = make_shardlocal_exchange(
        up, dn, mesh, {"w": P()}, {"w": P("data")}, "data", n,
        transport=transport_for_mode("shard_local"))
    closed = jax.make_jaxpr(ex)(
        {"w": jax.ShapeDtypeStruct((d,), jnp.float32)},
        {"w": jax.ShapeDtypeStruct((n, d), jnp.float32)},
        {"w": jax.ShapeDtypeStruct((n, d), jnp.float32)},
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    viols = check_collective_bytes(closed, "fixture", {
        "all_gather_fbytes": 64 * n, "psum_fbytes": 4096})
    assert [v.rule for v in viols] == ["collective-bytes"]
    assert "psum_fbytes" in viols[0].detail


# ---------------------------------------------------------------------------
# AST rule fixtures
# ---------------------------------------------------------------------------

def _rules(viols):
    return [v.rule for v in viols]


def test_ast_host_rng_in_traced_body():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x + np.random.rand()\n"
    )
    assert any(r.startswith("R001") for r in _rules(lint_source(src, "core/x.py")))
    # np.random OUTSIDE a traced body is fine (seeding, data gen)
    ok = "import numpy as np\ndef gen():\n    return np.random.rand()\n"
    assert lint_source(ok, "core/x.py") == []


def test_ast_host_time_in_traced_body():
    src = (
        "import time\n"
        "import jax\n"
        "def device_round(self, state, data, key):\n"
        "    t = time.time()\n"
        "    return state, {'t': t}\n"
    )
    assert any(r.startswith("R001")
               for r in _rules(lint_source(src, "fed/x.py")))


def test_ast_unresolvable_codec_spec():
    src = "cfg = FedConfig(n_clients=4, codec_up='no_such_codec:8')\n"
    assert any(r.startswith("R002") for r in _rules(lint_source(src, "x.py")))
    ok = "cfg = FedConfig(n_clients=4, codec_up='lattice:8')\n"
    assert lint_source(ok, "x.py") == []


def test_ast_metrics_keys_incomplete():
    src = (
        "def device_round(self, state, data, key):\n"
        "    metrics = {'sim_time': 0.0}\n"
        "    return state, metrics\n"
    )
    assert any(r.startswith("R003")
               for r in _rules(lint_source(src, "fed/x.py")))


def test_ast_unused_import():
    src = "import os\nimport sys\nprint(sys.argv)\n"
    viols = lint_source(src, "x.py")
    assert _rules(viols) == ["R004:unused-import"]
    assert "os" in viols[0].detail
    # noqa and __all__ re-exports are honored
    assert lint_source("import os  # noqa\n", "x.py") == []
    assert lint_source("import os\n__all__ = ['os']\n", "x.py") == []


# ---------------------------------------------------------------------------
# engine hooks used by the analyzers
# ---------------------------------------------------------------------------

def test_traced_hooks_are_side_effect_free():
    """traced_round/traced_chunk must not consume state or warm the run
    cache — the sentinel relies on fingerprinting before the run."""
    from repro.fed.engine import RoundEngine
    alg, data, params0, key = _build_cell("quafl", "lattice")
    eng = RoundEngine(_traceable(alg))
    state = eng.alg.init(params0)
    closed_r = eng.traced_round(state, data, key)
    closed_c = eng.traced_chunk(state, data, key, 2)
    assert closed_r.jaxpr.eqns and closed_c.jaxpr.eqns
    assert eng._chunk_fns == {}   # tracing never touched the jit cache
    # the state is still alive (not donated by tracing)
    _ = [leaf.block_until_ready()
         for leaf in jax.tree_util.tree_leaves(state)]


def test_matrix_covers_every_registry_algorithm():
    from repro.fed.registry import registered_algorithms
    algs = {a for a, _ in ALL_CELLS}
    assert algs == set(registered_algorithms()) - {"fedbuff"}
    assert set(MATRIX_CODECS) == {"lattice", "lattice_packed", "topk_ef"}
    # the heterogeneous-width cell rides quafl (the batched grouped path)
    assert ("quafl", "lattice_grouped") in ALL_CELLS


# ---------------------------------------------------------------------------
# flow engine + wire-truth / γ-interval / divergence analyzers
# ---------------------------------------------------------------------------

def test_collective_bytes_on_hand_built_jaxprs():
    """Byte accounting per collective on hand-built programs: reductions
    charge their input avals, gathers their output avals, split by element
    kind — and the walk reaches bodies nested under scan."""
    from repro.analysis.jaxpr import collective_bytes

    env = [("i", 4)]
    closed = jax.make_jaxpr(lambda x: jax.lax.psum(x, "i"),
                            axis_env=env)(jnp.ones(8, jnp.float32))
    assert collective_bytes(closed) == {"psum_fbytes": 8 * 4}

    closed = jax.make_jaxpr(lambda x: jax.lax.all_gather(x, "i"),
                            axis_env=env)(jnp.ones(16, jnp.int32))
    assert collective_bytes(closed) == {"all_gather_ibytes": 4 * 16 * 4}

    # lax.psum_scatter binds the reduce_scatter primitive — the byte gate
    # must charge that key, not a vacuous psum_scatter_* entry
    closed = jax.make_jaxpr(
        lambda x: jax.lax.psum_scatter(x, "i", tiled=True),
        axis_env=env)(jnp.ones(8, jnp.float32))
    assert collective_bytes(closed) == {"reduce_scatter_fbytes": 8 * 4}

    def scanned(x):
        def body(c, _):
            return c + jax.lax.psum(c, "i"), jax.lax.all_gather(c, "i")
        return jax.lax.scan(body, x, None, length=3)

    b = collective_bytes(jax.make_jaxpr(scanned, axis_env=env)(
        jnp.ones(8, jnp.float32)))
    assert b["psum_fbytes"] == 8 * 4
    assert b["all_gather_fbytes"] == 4 * 8 * 4


def test_flow_engine_scan_carry_fixpoint():
    """The worklist engine iterates scan carries to a fixpoint: a carry
    clamped into [0, 1] every iteration keeps that interval instead of
    widening to top."""
    from repro.analysis.intervals import interval_of

    def f(x):
        def body(c, _):
            return jnp.clip(c * 0.5, 0.0, 1.0), None

        y, _ = jax.lax.scan(body, x, None, length=8)
        return y

    (iv,) = interval_of(f, [(0.0, 1.0)], jnp.zeros(4))
    assert 0.0 <= iv[0] and iv[1] <= 1.0


def test_mutation_fp32_wire_leak_detected():
    """An fp32 array marked as the int codes payload is the wire-leak bug
    class: the audit flags kind AND container drift; the honest container
    at the same site is clean."""
    from repro.analysis.provenance import wire_mark
    from repro.analysis.wire import check_wire_truth
    from repro.compression.codecs import LatticeCodec

    codec = LatticeCodec(bits=8)
    d = 2048
    decl = codec.wire_declaration(d)

    def leaky(x):
        return wire_mark(x, channel="up", part="codes", codec=codec.name,
                         d=d)

    closed = jax.make_jaxpr(leaky)(jnp.ones(d, jnp.float32))
    viols = check_wire_truth(closed, where="fixture", decl_up=decl,
                             codec_up=codec, d=d)
    assert any("fp32 reaching the wire" in v.detail for v in viols)
    assert any("32-bit container" in v.detail for v in viols)

    def honest(x):
        return wire_mark(x.astype(jnp.uint8), channel="up", part="codes",
                         codec=codec.name, d=d)

    closed = jax.make_jaxpr(honest)(jnp.ones(d, jnp.float32))
    assert check_wire_truth(closed, where="ok", decl_up=decl,
                            codec_up=codec, d=d) == []


def test_grouped_levels_row_audited_not_exempted():
    """The grouped codec's per-message moduli row is charged wire traffic:
    the declaration carries a levels part (message_bits includes it), the
    traced row passes the audit — and a declaration WITHOUT the part trips
    the uncharged-side-channel rule."""
    from repro.analysis.provenance import wire_mark
    from repro.analysis.wire import check_wire_truth
    from repro.compression.codecs import GroupedLatticeCodec, WireDecl

    codec = GroupedLatticeCodec(bits_per_client=(4, 8),
                                wire_width_per_client=(4, 8))
    d = 1024
    decl = codec.wire_declaration(d)
    assert decl.part("levels") is not None
    assert decl.message_bits == codec.message_bits(d)
    assert decl.moduli == (16, 256)

    def ships(codes, gam, lev):
        wire_mark(codes, channel="up", part="codes", codec=codec.name,
                  batched=True, d=d)
        wire_mark(gam, channel="up", part="gamma", codec=codec.name,
                  batched=True, d=d)
        wire_mark(lev, channel="up", part="levels", codec=codec.name,
                  batched=True, d=d)
        return codes

    closed = jax.make_jaxpr(ships)(jnp.zeros((2, d), jnp.uint8),
                                   jnp.zeros((2,), jnp.float32),
                                   jnp.zeros((2,), jnp.float32))
    assert check_wire_truth(closed, where="ok", decl_up=decl) == []

    bald = WireDecl(codec=codec.name,
                    parts=tuple(p for p in decl.parts
                                if p.part != "levels"),
                    moduli=decl.moduli, safety=decl.safety)
    viols = check_wire_truth(closed, where="fixture", decl_up=bald)
    assert any("side-channel" in v.detail for v in viols)


def test_mutation_gamma_overflow_detected():
    """Interval analysis proves the encode path cannot wrap at the
    declared width — and fires when codes overflow the modulus or the
    safety factor is too small for Lemma 3.1's window."""
    from repro.analysis.intervals import (check_encode_intervals,
                                          check_gamma_window)
    from repro.compression.pipeline import ExchangePipeline, LatticeWire

    pipe = ExchangePipeline(bits=8, backend="jnp")
    wire8 = LatticeWire(bits=8, pack=1)
    assert check_encode_intervals(pipe, wire8, 2048, (256,), "ok") == []
    # 8-bit codes audited against a declared 4-bit modulus: overflow
    viols = check_encode_intervals(pipe, wire8, 2048, (16,), "fixture")
    assert [v.rule for v in viols] == ["gamma-overflow"]

    assert check_gamma_window(pipe, wire8, 2048, "ok") == []
    loose = ExchangePipeline(bits=8, backend="jnp", safety=1.5)
    viols = check_gamma_window(loose, wire8, 2048, "fixture")
    assert viols and all(v.rule == "gamma-overflow" for v in viols)


def test_mutation_divergent_escape_detected():
    """A value derived from axis_index committed through P() is device 0's
    copy published as replicated state; resolving it with a psum over the
    axis is clean."""
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.analysis.divergence import check_divergence
    from repro.utils.compat import shard_map

    mesh = AbstractMesh((4,), ("data",))

    def body(x):
        return x + jax.lax.axis_index("data").astype(jnp.float32)

    bad = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                    check_vma=False)
    viols = check_divergence(jax.make_jaxpr(bad)(jnp.ones(8)), "fixture")
    assert [v.rule for v in viols] == ["spmd-divergence"]
    assert "data" in viols[0].detail

    def resolved(x):
        return jax.lax.psum(
            x + jax.lax.axis_index("data").astype(jnp.float32), "data")

    ok = shard_map(resolved, mesh=mesh, in_specs=P(), out_specs=P(),
                   check_vma=False)
    assert check_divergence(jax.make_jaxpr(ok)(jnp.ones(8)), "ok") == []


def test_exchange_matrix_cells_clean():
    """Every codec × transport pair of the shard-local exchange passes the
    wire-truth, byte-budget, divergence and γ_rs checks on the abstract
    pod mesh."""
    from repro.analysis.lint import _exchange_cells, analyze_exchange_cell
    for codec, transport in _exchange_cells():
        rep = analyze_exchange_cell(codec, transport, d=1 << 14, n=4)
        assert rep["violations"] == [], (codec, transport,
                                         rep["violations"])


def test_engine_wire_provenance_hook():
    alg, data, params0, key = _build_cell("quafl", "lattice")
    from repro.fed.engine import RoundEngine
    t = _traceable(alg)
    closed, marks, colls = RoundEngine(t).wire_provenance(
        t.init(params0), data, key)
    assert closed.jaxpr.eqns
    parts = {p.get("part") for p, _, _ in marks}
    assert {"codes", "gamma"} <= parts
    assert all(p.get("d", 0) > 0 for p, _, _ in marks)


def test_lint_cell_listing_and_loud_only():
    from repro.analysis.lint import list_cells, run_lint
    cells = list_cells()
    assert "quaflxlattice_grouped" in cells
    assert "exchange:latticexreduce_scatter" in cells
    assert "rs_transport" in cells
    with pytest.raises(SystemExit):
        run_lint(quick=True, only="definitely_not_a_cell", verbose=False)


def test_report_is_deterministic_schema_v2():
    """The committed report must be byte-stable: schema v2, no wall-clock
    keys anywhere — timings go to the side dict the caller owns."""
    import json
    from repro.analysis.lint import run_lint
    timings = {}
    rep = run_lint(quick=True, only="sequentialxlattice", verbose=False,
                   timings=timings)
    assert rep["schema"] == "analysis.v2"
    assert '"seconds"' not in json.dumps(rep)
    assert rep["violations_total"] == 0
    assert timings and "total" in timings
