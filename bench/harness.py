"""The benchmark harness: one run of one cell.

Everything that belongs to a configuration, a traffic mix or a metric is a
file of its own, found by the names in ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the configuration as it is run; its
  ``system`` key names ``bench/systems/<system>.py``, which runs the
  program (with its reference ``<system>_reference.py``), and
  ``bench/configs/<config>.py`` is the model's plain reference and weights;
- ``bench/traffic/<traffic>.json``: the inputs and the work per round;
- ``bench/metrics/<metric>.py``: a reader ``read(run)`` for each metric;
- ``bench/limits/<workload>.json``: the limit of each number compared.

A run: set-up (weights, inputs, the program, its check rounds, which
compile and warm it up), then a window of ``--seconds`` in which the host
calls the program's own entry point again and again, waiting on each
call's result only after the next call has been issued. With ``--trace 1``
the window runs under the profiler. After the window the program's state
is freed and the reference replays the check rounds; the comparison
decides ``correct``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str):
    """(workload entry, configuration entry) of a cell."""
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}")
    cfg = next(c for c in spec["configs"] if c["name"] == wl["config"])
    return wl, cfg


def metrics_of(spec: dict, workload: str, kind: str):
    """The metric entries of ``kind`` (end_to_end | per_layer) this cell
    reports: all without a ``workloads`` list, else those that name it."""
    return [m for m in spec[kind]
            if workload in m.get("workloads", [workload])]


def load_cell(spec: dict, workload: str, seed: int, *, rehearse=False):
    """The system's Cell for a workload, not yet set up."""
    from bench import traffic as traffic_mod
    wl, entry = resolve(spec, workload)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    tr = traffic_mod.load(wl["traffic"])
    if rehearse:
        cfg, tr = _rehearsal(cfg), _rehearsal(tr)
    model = load_module(BENCH / "configs" / f"{entry['name']}.py",
                        f"bench_config_{entry['name'].replace('-', '_')}")
    system = importlib.import_module(f"bench.systems.{cfg['system']}")
    return system.Cell(cfg, tr, model, seed_key(seed))


def _rehearsal(doc: dict) -> dict:
    """A file's values with its ``rehearsal`` block laid over them (one
    level of nesting deep): the tiny CPU stand-in of a configuration or a
    traffic mix, for the tests."""
    out = dict(doc)
    for k, v in doc.get("rehearsal", {}).items():
        out[k] = {**doc[k], **v} if isinstance(v, dict) else v
    return out


def seed_key(seed: int):
    import jax
    return jax.random.PRNGKey(int(seed))


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class DeviceCell:
    """What the systems' cells share: waiting on a call's result or on the
    state, counting the calls whose uplink error is not finite, freeing
    the program's state, and the readings of the check rounds."""

    @staticmethod
    def wait(handle):
        import jax
        jax.block_until_ready(handle)

    def finish(self):
        self.wait(self.state)

    @staticmethod
    def failed(handles) -> int:
        import jax
        import numpy as np
        q = np.asarray(jax.device_get([h["quant_err"] for h in handles]))
        return int(np.sum(~np.isfinite(q)))

    def release(self):
        for name in ("state", "alg", "engine", "data"):
            self.__dict__.pop(name, None)

    def readings(self):
        return self.check


class GcLog:
    """Pauses of Python's garbage collector while it is registered."""

    def __init__(self):
        self.count, self.total_s, self.longest_s = 0, 0.0, 0.0
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            self.count += 1
            self.total_s += dt
            self.longest_s = max(self.longest_s, dt)


def _annotator(tracing: bool):
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def run_window(cell, seconds: float, tracing: bool = False):
    """Drive the program for ``seconds``; the window closes when the last
    call's state is ready. Call r's completion is observed by waiting on
    its result after call r + 1 has been issued, so the device never idles
    on the wait."""
    span = _annotator(tracing)
    handles, done, dispatch = [], [], []
    gc_log = GcLog()
    gc.callbacks.append(gc_log)
    with span("window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with span("data"):
                cell.prepare()
            t = time.perf_counter()
            with span("dispatch"):
                handles.append(cell.step())
            dispatch.append(time.perf_counter() - t)
            if len(handles) > 1:
                with span("wait"):
                    cell.wait(handles[-2])
                done.append(time.perf_counter())
            if time.perf_counter() >= deadline:
                break
        with span("wait"):
            cell.wait(handles[-1])
            cell.finish()
        t_end = time.perf_counter()
    gc.callbacks.remove(gc_log)
    done.append(t_end)
    per = cell.rounds_per_call
    stamps = [t0] + done
    round_times = [(b - a) / per for a, b in zip(stamps, stamps[1:])
                   for _ in range(per)]
    return SimpleNamespace(t0=t0, window_s=t_end - t0,
                           rounds=len(handles) * per, handles=handles,
                           round_times=round_times, dispatch_s=dispatch,
                           gc=gc_log)


def peak_bytes(devices) -> int:
    """The fullest chip's peak: its arrays' peak (``peak_bytes_in_use``)
    plus the peak held in reserve for the programs' temporaries
    (``peak_bytes_reserved``), which the first leaves out."""
    def one(d):
        s = d.memory_stats() or {}
        return s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
    return int(max(one(d) for d in devices))


def read_metrics(entries, run) -> dict:
    out = {}
    for m in entries:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        value = reader.read(run)
        if value is None:
            raise RuntimeError(f"metric {m['name']} found nothing to read "
                               f"in this run")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, *, t_start: float | None = None, rehearse=False) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="copy the profiler's trace to this directory")
    args = ap.parse_args(argv)

    spec = load_spec()
    wl, _ = resolve(spec, args.workload)
    if not rehearse:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if not rehearse and (dev.platform != "tpu" or len(devices) < wl["chips"]):
        print(f"no accelerator for this cell: JAX found {len(devices)} x "
              f"{dev.platform} ({dev.device_kind}), the cell needs "
              f"{wl['chips']} TPU chip(s)", file=sys.stderr, flush=True)
        return 3
    sys.path.insert(0, str(ROOT / "src"))
    if not rehearse:
        from repro.utils.cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import correct, trace as trace_mod

    clog = CompileLog()
    cell = load_cell(spec, args.workload, args.seed, rehearse=rehearse)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    compiles_setup = clog.compiles
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    log(f"[setup] {setup_s:.3f} s; {compiles_setup} backend compiles "
        f"({clog.seconds:.3f} s), {clog.cache_hits} persistent-cache hits")

    tracing = bool(args.trace)
    trace_dir = None
    seconds = args.seconds
    if tracing:
        import tempfile
        seconds = min(seconds, cell.traffic.get("trace_seconds", seconds))
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    win = run_window(cell, seconds, tracing)
    if tracing:
        jax.profiler.stop_trace()
    window_compiles = clog.compiles - compiles_setup
    slow = max(range(len(win.round_times)), key=win.round_times.__getitem__)
    log(f"[window] {win.rounds} rounds in {win.window_s:.6f} s; "
        f"{window_compiles} backend compiles inside the window; longest "
        f"round {win.round_times[slow]:.6f} s (round {slow + 1}), median "
        f"{sorted(win.round_times)[len(win.round_times) // 2]:.6f} s; "
        f"python gc {win.gc.count} pauses, {win.gc.total_s:.6f} s in all, "
        f"longest {win.gc.longest_s:.6f} s")
    memory = peak_bytes(devices[: wl["chips"]])
    log(f"[memory] {devices[0].memory_stats()}")
    attempted, failed = win.rounds, cell.failed(win.handles)

    summary = None
    if tracing:
        import shutil
        if args.keep_trace:
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
        summary = trace_mod.reduce(trace_dir)
        summary["rounds"] = win.rounds
        shutil.rmtree(trace_dir, ignore_errors=True)

    peaks = json.loads((BENCH / "peaks.json").read_text())
    run = SimpleNamespace(setup_s=setup_s, window=win, cell=cell,
                          trace=summary,
                          peak=peaks["devices"].get(dev.device_kind))
    if run.peak is None and not rehearse:
        raise RuntimeError(f"device kind {dev.device_kind!r} is not in "
                           f"bench/peaks.json")
    kind = "per_layer" if tracing else "end_to_end"
    metrics = read_metrics(metrics_of(spec, args.workload, kind), run)

    prog = cell.readings()
    del win
    cell.release()
    jax.clear_caches()    # loaded programs hold device memory of their own
    gc.collect()
    t_ref = time.perf_counter()
    ref = cell.reference()
    log(f"[reference] {time.perf_counter() - t_ref:.3f} s")
    values = correct.gaps(prog, ref)
    lims = correct.limits(args.workload)
    ok = correct.judge(values, lims)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": wl["chips"], "memory_peak_bytes": memory}
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if tracing:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["window_compiles"] = window_compiles
    result["checks"] = {k: {"value": values[k], "limit": lims[k]}
                        for k in lims}
    for k in lims:
        log(f"check {k}: {values[k]!r} (limit {lims[k]!r})")
    print(json.dumps(result), flush=True)
    return 0
