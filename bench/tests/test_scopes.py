"""CPU tests of ``bench/scopes.py``: the reader of the program's named
layers, on traces recorded on a TPU v5e. Run as ``pytest bench/tests``."""
from __future__ import annotations

from collections import defaultdict

import pytest

from bench import harness, scopes, trace
from repro.utils import spans

DATA = harness.BENCH / "tests" / "data"
# two rounds of `olmo1b-exchange` from a program that named no layers
UNSCOPED = DATA / "olmo1b-exchange.xplane.pb.gz"


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(round)/fl.exchange/vmap(jit(fused_decode))/pallas_call",
     spans.EXCHANGE),
    ("jit(round)/fl.exchange/vmap(fl.exchange.noise)/jit(_uniform)/or:",
     spans.NOISE),
    ("jit(run)/while/body/closed_call/jit(round)/vmap(fl.local_steps)/while"
     "/body/closed_call/transpose(jvp())/dot_general:", spans.LOCAL_STEPS),
    ("jit(_round)/fl.local_steps/while/body/closed_call/transpose("
     "jvp(fl.local_steps))/mul:", spans.LOCAL_STEPS),
    ("jit(round)/fl.population/jit(_shuffle)/while", spans.POPULATION),
    # merged ops: the first path is read
    ("jit(round)/reshape;jit(round)/fl.exchange/squeeze:", None),
    ("jit(round)/fl.exchange/squeeze;reshape:", spans.EXCHANGE),
    ("jit(round)/fl.exchanges/add:", None),
    ("", None),
])
def test_an_op_belongs_to_its_innermost_scope(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def _by_name(path):
    """Device seconds of the window's ops, grouped by what their name-stack
    path says: a Pallas kernel, a random draw, anything else."""
    out = defaultdict(float)
    for secs, _, tf_op, _ in scopes.ops_in_window(
            scopes.read(str(path)))[0]:
        if "pallas_call" in tf_op:
            out["pallas"] += secs
        elif "jit(_rademacher)" in tf_op or "jit(_uniform)" in tf_op:
            out["rng"] += secs
        else:
            out["other"] += secs
    return out


def test_op_paths_of_a_recorded_chip_trace():
    """The decoder's ``tf_op`` paths: the Pallas ops are the kernels
    ``bench/trace.py`` counts, the random draws of the exchange take half
    the device time, and the ops add up to the busy time."""
    got = _by_name(UNSCOPED)
    ref = trace.reduce(str(UNSCOPED))
    assert got["pallas"] == pytest.approx(ref["kernel_s"], abs=1e-7)
    assert got["pallas"] == pytest.approx(0.20697, abs=1e-5)
    assert got["rng"] == pytest.approx(0.36987, abs=1e-5)
    assert sum(got.values()) == pytest.approx(ref["busy_s"], rel=1e-3)


def test_a_program_without_scopes_reads_as_unscoped():
    got = scopes.reduce(str(UNSCOPED))
    ref = trace.reduce(str(UNSCOPED))
    assert got["window_s"] == pytest.approx(ref["window_s"], abs=1e-9)
    assert got["busy_s"] == pytest.approx(ref["busy_s"], abs=1e-6)
    assert got["rounds"] == ref["rounds"] == 2
    assert set(got["scopes"]) == set(spans.SCOPES)
    assert not any(got["scopes"].values())
    assert got["unscoped_s"] == pytest.approx(got["busy_s"], rel=1e-3)
    # no program span to name a gap by: the harness's spans alone
    assert [g for g, _ in got["idle_gaps"]] == [g for g, _ in
                                                ref["idle_gaps"]]


def _top(got):
    """Device seconds of the outermost scopes and of the unscoped rest:
    together, every op once."""
    return got["unscoped_s"] + sum(
        t for s, t in got["scopes"].items()
        if not any(s.startswith(p + ".") for p in spans.SCOPES))


# device seconds of (fl.local_steps, fl.exchange, fl.exchange.noise,
# fl.population, unscoped) in each scoped trace
SCOPED = {
    "olmo1b-exchange": (0.033639711, 0.690526925, 0.369867435, 0.0,
                        0.042593932),
    "paper-mlp-leaf300": (0.041192394, 0.013334338, 0.002801087,
                          0.005973458, 0.006871301),
}


@pytest.mark.parametrize("cell", sorted(SCOPED))
def test_scopes_of_a_recorded_chip_trace(cell):
    """The program with its layers named, traced on a TPU v5e: two rounds
    of `olmo1b-exchange` (`--seconds 0.01`), two 64-round chunks of
    `paper-mlp-leaf300` (`--seconds 0.001`). The scopes and the unscoped
    rest add up to the busy time, the noise lies inside the exchange, and
    the program's host spans share the device's clock: each eager round
    or chunk runs inside the harness's `dispatch` span."""
    path = str(DATA / f"{cell}.scoped.xplane.pb.gz")
    got = scopes.reduce(path)
    ref = trace.reduce(path)
    assert got["busy_s"] == pytest.approx(ref["busy_s"], abs=1e-6)
    assert _top(got) == pytest.approx(got["busy_s"], rel=1e-9)
    *named, unscoped = SCOPED[cell]
    assert [got["scopes"][s] for s in spans.SCOPES] == pytest.approx(
        named, abs=1e-9)
    assert got["unscoped_s"] == pytest.approx(unscoped, abs=1e-9)
    assert got["scopes"][spans.NOISE] < got["scopes"][spans.EXCHANGE]
    tr = scopes.read(path)
    lo, hi = scopes.window(tr)
    dispatch = tr.host["dispatch"]
    program = [iv for k in spans.HOST_SPANS for iv in tr.host[k]
               if lo <= iv[0] < hi]
    assert len(program) == len(dispatch) == got["rounds"] == 2
    assert all(any(a <= s and e <= b for a, b in dispatch)
               for s, e in program)


def test_a_program_span_names_a_gap():
    """In `paper-mlp-leaf300` the device waits while the host dispatches
    the window's first chunk: the gap is `dispatch/fl.chunk`."""
    got = scopes.reduce(str(DATA / "paper-mlp-leaf300.scoped.xplane.pb.gz"))
    assert got["idle_gaps"][1][0] == f"dispatch/{spans.CHUNK}"
    assert got["idle_gaps"][1][1] == pytest.approx(0.000682292, abs=1e-9)
