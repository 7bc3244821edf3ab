"""CPU tests of the benchmark: the spec, the configuration files against
the program, each cell's run through the harness at rehearsal size, the
faults that ``correct`` has to catch, and the refusal to run without a
TPU. Run as ``pytest bench/tests``.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import correct, harness
from bench.precision import MODES

SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_spec_files_exist_for_every_name():
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        assert (harness.ROOT / c["file"]).is_file()
        assert (harness.BENCH / "configs" / f"{c['name']}.py").is_file()
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert (harness.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert set(correct.limits(w["name"]))
        assert set(correct.controls(w["name"])) <= set(MODES) - {"f32"}
    for kind in ("end_to_end", "per_layer"):
        for m in SPEC[kind]:
            assert NAME.match(m["name"])
            assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
            assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


def _run(workload, seconds=1.0, trace=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", workload, "--seed", "2147483711",
                           "--seconds", str(seconds), "--trace", str(trace)],
                          rehearse=True)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal_is_correct(workload):
    res = _run(workload)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["window_compiles"] == 0
    assert list(res)[-1] == "checks"
    names = {m["name"] for m in harness.metrics_of(SPEC, workload,
                                                   "end_to_end")}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())


def _fault_unchanged(monkeypatch):
    """Every round returns the state it was given."""
    from repro.core.quafl import QuAFL
    from repro.launch.spmd import SpmdAlgorithm
    for cls in (QuAFL, SpmdAlgorithm):
        orig = cls.device_round

        def same(self, state, data, key, orig=orig):
            _, metrics = orig(self, state, data, key)
            return state, metrics

        monkeypatch.setattr(cls, "device_round", same)


def _fault_half_batch(monkeypatch):
    """Local steps see half of each batch, the mean taken over it."""
    import repro.launch.steps as steps
    import repro.models.mlp as mlp
    lm_loss, mlp_loss = steps.lm_loss, mlp.mlp_loss

    def lm_half(cfg, p, batch, **kw):
        t = batch["tokens"]
        return lm_loss(cfg, p, {"tokens": t[: t.shape[0] // 2]}, **kw)

    def mlp_half(p, batch):
        b = batch["y"].shape[0] // 2
        return mlp_loss(p, {k: v[:b] for k, v in batch.items()})

    monkeypatch.setattr(steps, "lm_loss", lm_half)
    monkeypatch.setattr(mlp, "mlp_loss", mlp_half)


def _fault_wrong_rows(monkeypatch):
    """The polled clients' new models are scattered one row off."""
    import jax.numpy as jnp
    import repro.core.quafl as quafl
    scatter = quafl.scatter_rows

    def off_by_one(pop, idx, updates):
        return scatter(pop, jnp.roll(idx, 1), updates)

    monkeypatch.setattr(quafl, "scatter_rows", off_by_one)


def _batch(workload):
    wl, _ = harness.resolve(SPEC, workload)
    return json.loads((harness.BENCH / "traffic" / f"{wl['traffic']}.json")
                      .read_text())["batch"]


def _population(workload):
    wl, cfg = harness.resolve(SPEC, workload)
    return json.loads((harness.ROOT / cfg["file"]).read_text())[
        "system"] == "quafl_flat"


# half of a batch of one row is no batch: that fault exists only where
# the cell's batch has several rows; rows exist only in a population
FAULTS = ([(w, "unchanged") for w in CELLS]
          + [(w, "half_batch") for w in CELLS if _batch(w) > 1]
          + [(w, "wrong_rows") for w in CELLS if _population(w)])


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_fault_in_timed_path_is_not_correct(workload, fault, monkeypatch):
    {"unchanged": _fault_unchanged,
     "half_batch": _fault_half_batch,
     "wrong_rows": _fault_wrong_rows}[fault](monkeypatch)
    res = _run(workload)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("workload,control",
                         [(w, c) for w in CELLS for c in correct.controls(w)])
def test_control_precision_is_not_correct(workload, control):
    """The reference in each of the cell's controls' precision (the model's
    matmuls in float8, the exchange's rotations at fewer bfloat16 passes)
    fails the cell's limits at rehearsal size."""
    cell = harness.load_cell(SPEC, workload, 5, rehearse=True)
    ref = cell.reference()
    control = cell.reference(control)
    assert not correct.judge(correct.gaps(control, ref),
                             correct.limits(workload))


def test_olmo_flops_count_the_programs_matmul_weights():
    from repro.models.model import abstract_lm
    from bench.systems.spmd_lm import model_config
    cfg = json.loads((harness.BENCH / "configs" / "olmo-1b-widths.json").read_text())
    model = harness.load_module(harness.BENCH / "configs" / "olmo-1b-widths.py",
                                "olmo_ref")
    spec, _ = abstract_lm(model_config(cfg))
    assert {k: tuple(v.shape) for k, v in spec.items()} == model.shapes(cfg)
    n = sum(v.size for v in spec.values() if len(v.shape) >= 2)
    assert model.matmul_params(cfg) == n == 505_675_776
    assert model.flops_per_token(cfg, 2048) == 6 * n + 12 * 6 * 2048 * 2048


def _no_result(cmd, cwd, env):
    p = subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    _no_result([sys.executable, "bench/run.py", "--workload", CELLS[0],
                "--seed", "1", "--seconds", "1", "--trace", "0"],
               harness.ROOT, env)


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    _no_result([sys.executable, "bench/run.py", "--workload", CELLS[0],
                "--seed", "1", "--seconds", "1", "--trace", "0"],
               tmp_path, env)


TRACE = harness.BENCH / "tests" / "data" / "olmo1b-exchange.xplane.pb.gz"


def test_trace_reduction_of_a_recorded_chip_trace():
    """Two rounds of `olmo1b-exchange` traced on a TPU v5e (`--seconds
    0.01 --trace 1 --keep-trace`): the window, the device's busy time, the
    Pallas kernels (32 a round: 8 leaves, encode and decode both ways) and
    the ops, as the reduction reads them."""
    from bench import trace
    got = trace.reduce(str(TRACE))
    assert got["devices"] == 1 and got["rounds"] == 2
    assert got["window_s"] == pytest.approx(0.770254691, abs=1e-9)
    assert got["busy_s"] == pytest.approx(0.766656141, abs=1e-9)
    assert got["kernel_calls"] == 64
    assert got["kernel_s"] == pytest.approx(0.20697127, abs=1e-9)
    assert got["device_ops"][0] == [
        "fusion f32[1,100663296]{1,0:T(1,128)}", pytest.approx(0.19602335)]
    assert sum(t for _, t in got["device_ops"]) <= got["busy_s"]
    assert all(not name.startswith("while")
               for name, _ in got["device_ops"])
    assert got["idle_gaps"][0][0] == "wait"
    assert len(got["device_ops"]) == len(got["idle_gaps"]) == trace.TOP
