"""Benchmark harness entry point — one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick] [--only SUBSTR]``
prints ``name,us_per_call,derived`` CSV (+ ``# curve:`` blocks carrying the
convergence data each paper figure plots) and writes every emitted row to a
machine-readable JSON baseline so subsequent PRs have a perf trajectory to
diff against: the ``algorithms`` and ``population`` benches (the whole
registry under one clock; the population engine's scale/participation rows)
land in ``BENCH_algorithms.json``, everything else in
``BENCH_exchange.json``. ``--only`` filters benchmarks by name substring
(e.g. ``--only exchange``, ``--only population``); record names are the
baselines' merge keys, so duplicates across benches abort the run.
"""
import json
import os
import sys
import time

from benchmarks import (bench_algorithms, bench_analysis, bench_averaging,
                        bench_bits, bench_bits_accounting, bench_exchange,
                        bench_extensions, bench_fedbuff, bench_kernels,
                        bench_local_steps, bench_peers, bench_population,
                        bench_quantizer, bench_roofline, bench_swt,
                        bench_time)
from benchmarks.common import RECORDS
from repro.utils.cache import enable_compile_cache

BENCHES = [
    ("Fig1_peers", bench_peers.main),
    ("Fig2_bits", bench_bits.main),
    ("Fig3_time", bench_time.main),
    ("Fig4_averaging", bench_averaging.main),
    ("Fig5_quantizer", bench_quantizer.main),
    ("Fig6_fedbuff", bench_fedbuff.main),
    ("Fig7_local_steps", bench_local_steps.main),
    ("Fig9_swt", bench_swt.main),
    ("Lemma38_bits", bench_bits_accounting.main),
    ("ext_scaffold_adaptive", bench_extensions.main),
    ("kernels", bench_kernels.main),
    ("exchange", bench_exchange.main),
    ("algorithms", bench_algorithms.main),
    ("population", bench_population.main),
    ("roofline", bench_roofline.main),
    ("analysis", bench_analysis.main),
]

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON_PATH = os.path.join(_ROOT, "BENCH_exchange.json")
# benches whose records get their own baseline file (name -> path)
JSON_TARGETS = {"algorithms": os.path.join(_ROOT, "BENCH_algorithms.json"),
                "population": os.path.join(_ROOT, "BENCH_algorithms.json"),
                "analysis": os.path.join(_ROOT, "ANALYSIS.json")}
# quick-scale numbers are not comparable with the committed baselines, so
# they land under the gitignored bench_out/ instead of the repo root
QUICK_DIR = os.path.join(_ROOT, "bench_out")


def _arg_value(flag: str):
    if flag in sys.argv:
        i = sys.argv.index(flag)
        if i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return None


def _write_merged(path: str, records, quick: bool):
    """Merge records by name into ``path`` — a partial run (--only)
    refreshes its own rows without clobbering the committed baseline.
    Top-level keys beyond schema/quick/benches are preserved, so routing
    records into a richer report (ANALYSIS.json carries the full analyzer
    payload next to its bench rows) doesn't flatten it."""
    base = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                base = json.load(f)
        except ValueError:
            base = {}
    try:
        merged = {r["name"]: r for r in base.get("benches", [])}
    except (KeyError, TypeError):
        merged = {}
    merged.update({r["name"]: r for r in records})
    base.setdefault("schema", "bench.v1")
    base["quick"] = quick
    base["benches"] = list(merged.values())
    with open(path, "w") as f:
        json.dump(base, f, indent=2)
    print(f"# wrote {len(records)} records ({len(merged)} total) to {path}")


def main() -> None:
    enable_compile_cache()
    quick = "--quick" in sys.argv
    only = _arg_value("--only")
    print("name,us_per_call,derived")
    by_target = {}   # json path -> records
    for name, fn in BENCHES:
        if only and only not in name:
            continue
        t0 = time.time()
        n_before = len(RECORDS)
        print(f"# === {name} ===")
        try:
            if fn.__code__.co_argcount and quick:
                fn(20)
            else:
                fn()
        except Exception as e:  # keep the harness going
            print(f"{name},0.0,ERROR={type(e).__name__}:{e}")
        target = JSON_TARGETS.get(name, JSON_PATH)
        by_target.setdefault(target, []).extend(RECORDS[n_before:])
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if not RECORDS:
        print("# no records emitted (bad --only filter?); leaving JSON "
              "baselines untouched")
        return
    # record names are the merge keys of the committed baselines: a
    # duplicate would silently overwrite another bench's row, so fail loud
    names = [r["name"] for r in RECORDS]
    dups = sorted({n for n in names if names.count(n) > 1})
    if dups:
        raise SystemExit(f"duplicate bench record names {dups}: two "
                         f"benches would clobber each other's baseline row")
    for path, records in by_target.items():
        if not records:
            continue
        if quick:
            os.makedirs(QUICK_DIR, exist_ok=True)
            path = os.path.join(
                QUICK_DIR,
                os.path.basename(path).replace(".json", ".quick.json"))
        _write_merged(path, records, quick)


if __name__ == "__main__":
    main()
