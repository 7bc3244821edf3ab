"""Generalized op-budget audit: trace-time structural counters + budgets.

PR 1 introduced a bespoke ``RotationStats`` counter inside
``compression/pipeline.py`` to pin the rotated-exchange invariant (``s + 1``
forward / ``s + 1`` inverse full-model rotation passes per QuAFL round).
This module is its promoted, general home: :class:`OpBudget` is the same
trace-time counter idea (counts are *structural* — incremented while python
builds the trace, so they are data-independent and free at runtime) behind
named counters, and :func:`check_rotation_budget` re-traces a round and
judges the counts against the declared budget, returning analyzer
:class:`~repro.analysis.jaxpr.Violation` records instead of bare asserts.

The jaxpr-level half of the budget — transfer / ``convert_element_type`` /
collective counts, which make e.g. the known fp32 re-gather after
``psum_scatter`` visible as a counted quantity — comes from
:func:`repro.analysis.jaxpr.op_report` and is merged into the same report
by :func:`op_budget_report`.

``ExchangePipeline`` keeps exposing the counter as ``pipeline.stats`` with
the legacy ``.fwd`` / ``.inv`` / ``.reset()`` surface, so existing tests
and any external consumers are unaffected by the promotion.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from repro.analysis.jaxpr import Violation, collective_bytes, op_report
from repro.kernels import exchange

# counter names the rotation audit uses
ROT_FWD = "rotation_fwd"
ROT_INV = "rotation_inv"
# counter names of the exchange kernels' grids: steps and Hadamard blocks
EXCHANGE_GRID_STEPS = exchange.GRID_STEPS
EXCHANGE_BLOCKS = exchange.GRID_BLOCKS


@dataclass
class OpBudget:
    """Named trace-time structural counters.

    Drop-in replacement for the old ``RotationStats``: ``.fwd`` / ``.inv``
    read and write the ``rotation_fwd`` / ``rotation_inv`` counters (so
    ``stats.fwd += m`` call sites and tests keep working verbatim), while
    arbitrary additional counters go through :meth:`add` / :meth:`get`.
    """
    counters: Dict[str, int] = field(default_factory=dict)

    def add(self, name: str, k: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(k)

    def get(self, name: str) -> int:
        return self.counters.get(name, 0)

    def reset(self) -> None:
        self.counters.clear()

    # legacy RotationStats surface -----------------------------------------
    @property
    def fwd(self) -> int:
        return self.get(ROT_FWD)

    @fwd.setter
    def fwd(self, v: int) -> None:
        self.counters[ROT_FWD] = int(v)

    @property
    def inv(self) -> int:
        return self.get(ROT_INV)

    @inv.setter
    def inv(self, v: int) -> None:
        self.counters[ROT_INV] = int(v)

    def expect(self, where: str,
               budget: Dict[str, int]) -> List[Violation]:
        """Judge the current counters against ``budget`` (exact match per
        named counter); returns one violation per blown counter."""
        out = []
        for name, want in budget.items():
            got = self.get(name)
            if got != want:
                out.append(Violation(
                    "op-budget", where,
                    f"counter {name!r}: {got} != budgeted {want}"))
        return out


@contextmanager
def exchange_grid_counts(budget: Optional[OpBudget] = None):
    """Count the exchange kernels' grids while the block traces: each call
    that reaches a wrapper of :mod:`repro.kernels.exchange` adds its grid
    steps and Hadamard blocks, so blocks per step read off as
    ``EXCHANGE_BLOCKS / EXCHANGE_GRID_STEPS``. A wrapper traced under
    ``vmap`` counts its unbatched call once."""
    budget = OpBudget() if budget is None else budget
    exchange.GRID_SINKS.append(budget)
    try:
        yield budget
    finally:
        exchange.GRID_SINKS.remove(budget)


def check_collective_bytes(closed, where: str,
                           caps: Dict[str, int]) -> List[Violation]:
    """Judge a trace's per-device collective payload
    (:func:`repro.analysis.jaxpr.collective_bytes`) against byte CAPS —
    upper bounds, not exact counts, because scalar side-channel rows may
    legitimately come and go. One violation per blown cap; a cap on a key
    the trace never produces passes vacuously (0 bytes moved)."""
    rep = collective_bytes(closed)
    out = []
    for key, cap in caps.items():
        got = rep.get(key, 0)
        if got > cap:
            out.append(Violation(
                "collective-bytes", where,
                f"{key}: {got} B moved exceeds budget {cap} B"))
    return out


def rotation_budget(s: int) -> Dict[str, int]:
    """The rotated-exchange contract per QuAFL round: one shared forward
    rotation feeds every uplink encode (clients reply in rotated space) and
    the s+1 averaged states rotate back once — ``s + 1`` fwd (s client
    encodes + the cached rotated-server downlink) / ``s + 1`` inv."""
    return {ROT_FWD: s + 1, ROT_INV: s + 1}


def _unjitted_round(alg):
    """The algorithm's round body as plain python, so tracing it ALWAYS
    re-runs the body and re-increments the trace-time counters — a jitted
    (or jit-forwarding) method whose (self, avals) signature is already in
    the pjit trace cache would skip the python body entirely."""
    for name in ("device_round", "round"):
        fn = getattr(type(alg), name, None)
        raw = getattr(fn, "__wrapped__", None)
        if raw is not None:
            # jitted method with static self (``@partial(jax.jit,
            # static_argnums=0)``) — rebind
            return lambda st, d, k: raw(alg, st, d, k)
    return getattr(alg, "device_round", None) or alg.round


def measure_round_counters(alg, state, data, key) -> Optional[OpBudget]:
    """Trace one round of ``alg`` and return the pipeline counters it
    incremented, or None when the algorithm has no counted pipeline."""
    import jax
    pipe = getattr(alg, "pipeline", None)
    stats = getattr(pipe, "stats", None)
    if stats is None:
        return None
    saved = dict(getattr(stats, "counters", {}))
    stats.reset()
    try:
        jax.eval_shape(_unjitted_round(alg), state, data, key)
        measured = OpBudget(dict(stats.counters))
    finally:
        stats.counters = saved
    return measured


def check_rotation_budget(alg, state, data, key, where: str,
                          budget: Optional[Dict[str, int]] = None,
                          ) -> List[Violation]:
    """Re-trace one round and audit the rotation-pass counters against the
    budget (default: :func:`rotation_budget` for the algorithm's ``s``).
    Algorithms without a counted pipeline pass vacuously."""
    measured = measure_round_counters(alg, state, data, key)
    if measured is None:
        return []
    if budget is None:
        budget = rotation_budget(int(alg.fed.s))
    return measured.expect(where, budget)


def op_budget_report(alg, state, data, key, closed) -> Dict[str, int]:
    """Merged structural report: jaxpr-level tracked op counts plus the
    pipeline's trace-time rotation counters (when present)."""
    rep = dict(op_report(closed))
    measured = measure_round_counters(alg, state, data, key)
    if measured is not None:
        rep.update({k: v for k, v in measured.counters.items()})
    return rep
