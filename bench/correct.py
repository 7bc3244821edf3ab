"""The comparison that decides ``correct``.

Both sides report the same readings of the first rounds of training:

- ``quant_err``: per round, the norm of the uplink's decode error;
- ``update``: per leaf, the norm of the server's change in round 1, the
  first gradient step as the server takes it in;
- ``change``: per model (server, each client) and leaf, the norm of the
  change over the check rounds; a model may be a population, one norm
  per row.

Each is reduced to one number, the worst gap over rounds or leaves. A norm
gap is |program - reference| over the larger of the reference's norm for
that leaf and the median leaf's, so that a leaf that hardly moves does not
read large on rounding alone. Leaves that the reference moves, but by less
than a thousandth of the median leaf, are left out: a step there is
round-off. One that it leaves exactly where it was (a client row never
polled) has to stay there.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parent / "limits"
SKIP_SHARE = 1e-3


def _norm_gap(prog: dict, ref: dict) -> float:
    ref_v = np.asarray([ref[k] for k in ref], np.float64)
    med = float(np.median(ref_v))
    worst = 0.0
    for k, r in ref.items():
        r = float(r)
        if 0 < r < SKIP_SHARE * med:
            continue
        p = float(prog[k])
        if not math.isfinite(p):
            return math.inf
        worst = max(worst, abs(p - r) / max(r, med))
    return worst


def _flat(readings: dict) -> dict:
    """{model/leaf[/row]: norm} over every model of a ``change`` reading."""
    out = {}
    for m, leaves in readings.items():
        for k, v in leaves.items():
            v = np.asarray(v, np.float64)
            if v.ndim == 0:
                out[f"{m}/{k}"] = float(v)
            else:
                out.update({f"{m}/{k}/{i}": float(x)
                            for i, x in enumerate(v)})
    return out


def gaps(prog: dict, ref: dict) -> dict:
    out = {}
    q_p = np.asarray(prog["quant_err"], np.float64)
    q_r = np.asarray(ref["quant_err"], np.float64)
    out["quant_err_gap"] = (float(np.max(np.abs(q_p - q_r) / q_r))
                            if np.all(np.isfinite(q_p)) else math.inf)
    if "update" in ref:
        out["update_gap"] = _norm_gap(prog["update"], ref["update"])
    out["change_gap"] = _norm_gap(_flat(prog["change"]), _flat(ref["change"]))
    return out


def _limits_file(workload: str) -> dict:
    return json.loads((LIMITS_DIR / f"{workload}.json").read_text())


def limits(workload: str) -> dict:
    return _limits_file(workload)["limits"]


def controls(workload: str) -> list:
    """The controls (``bench/precision.py`` modes) that have to fail the
    cell's limits."""
    return _limits_file(workload)["controls"]


def judge(values: dict, lims: dict) -> bool:
    return all(math.isfinite(values[k]) and values[k] <= lims[k]
               for k in lims)
