"""Readings that set the limits of a cell's comparison (not part of a run).

    python3 bench/calibrate.py --workload <name> --seeds 1 2 ... \\
        [--control-seeds 1 2 3] [--faults half_batch wrong_rows] [--out f.json]

For every seed, in one process: the program's set-up (its check rounds, at
the cell's own size), then the reference; the gaps between them are the
lower readings. For each control seed the reference is also computed in
each control's precision (``bench/precision.py``), and for each fault
with the fault planted in the reference put in the program's place; their
gaps to the sound reference are the upper readings. Prints one JSON line
per reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import correct, harness  # noqa: E402
from bench.precision import CONTROLS  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if not args.rehearse:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    import jax
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    sys.path.insert(0, str(harness.ROOT / "src"))
    spec = harness.load_spec()
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    for seed in args.seeds:
        cell = harness.load_cell(spec, args.workload, seed,
                                 rehearse=args.rehearse)
        t = time.perf_counter()
        cell.setup()
        prog = cell.readings()
        cell.release()
        jax.clear_caches()
        gc.collect()
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        ref = cell.reference()
        t_ref = time.perf_counter() - t
        emit({"seed": seed, "side": "program", **correct.gaps(prog, ref),
              "program_s": t_prog, "reference_s": t_ref})
        if seed in args.control_seeds:
            for mode in CONTROLS:
                emit({"seed": seed, "side": mode,
                      **correct.gaps(cell.reference(mode), ref)})
            for fault in args.faults:
                emit({"seed": seed, "side": fault,
                      **correct.gaps(cell.reference(fault=fault), ref)})
        del cell, prog, ref
    if args.out:
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
