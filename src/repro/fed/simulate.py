"""Generic simulation harness: run ANY registered algorithm to a budget.

The paper's comparisons (§5, App. A) hold the *budget* fixed — equal
simulated wall-clock, or equal communication bits — and let each algorithm
spend it its own way (QuAFL polls often and cheaply; FedAvg waits for
stragglers; FedBuff flushes a buffer). :func:`simulate` runs one
:class:`repro.fed.FedAlgorithm` until its budget is exhausted and emits ONE
trace format; :func:`compare` does it for a named set of algorithms under
identical seeds and budgets, which is the apples-to-apples harness every
figure-style experiment drives.

A trace row is a plain dict with the standardized metrics schema keys
(:data:`repro.fed.api.METRIC_KEYS`, all PER-ROUND exactly as the algorithm
returned them) plus ``round``, ``wall_time_s`` (host wall-clock when the
row was recorded), the CUMULATIVE counters ``bits_up_total`` /
``bits_down_total``, and whatever the optional ``eval_fn`` returns (dict
results are merged in; scalars land under ``"eval"``).

**Execution engines.** Two paths produce that trace:

  * **eager** (default) — one python-loop iteration per round. Any
    algorithm runs here, including host-control ones (python FedBuff's
    event heap, the adaptive bit-width walk).
  * **scanned** (``scan_chunk=K``) — for algorithms with the
    ``device_round`` capability (:mod:`repro.fed.engine`), rounds run in
    jitted ``lax.scan`` chunks of up to K rounds with ONE host sync per
    chunk; the chunk entry DONATES the carried state buffers (the previous
    chunk's output is consumed, not copied — rows and evals are recorded
    from the returned state before the next chunk reuses it). The key-split schedule matches the eager loop, so a scanned run
    is bit-for-bit the eager run under the same seed (exact in the
    equivalence tests for uncompressed/qsgd rounds; the rotation-fused
    lattice kernels agree to float32 rounding at chunk lengths >= 2, where
    XLA fuses the loop body differently than the standalone round);
    per-round row semantics are preserved (``record_every=1`` still yields
    one exact row per round, rebuilt from the chunk's stacked metrics).
    Differences:
    ``until_sim_time`` / ``until_bits`` budgets are only CHECKED at chunk
    boundaries (the run may overshoot by up to one chunk), chunks shrink to
    land ``eval_fn`` rounds on chunk boundaries, and ``wall_time_s`` is the
    chunk's recording time for every row in the chunk. Algorithms without
    the capability silently fall back to the eager path.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import numpy as np

from repro.fed.api import FedAlgorithm, normalize_metrics
from repro.fed.engine import RoundEngine, supports_scan
from repro.utils.spans import EVAL, SYNC


@dataclass
class Trace:
    """The single trace format every simulation emits."""
    algorithm: str
    rows: List[Dict[str, Any]] = field(default_factory=list)
    final_state: Any = None
    rounds: int = 0
    wall_time_s: float = 0.0
    eval_time_s: float = 0.0   # host time spent inside eval_fn
    engine: str = "eager"      # 'eager' | 'scanned'
    scan_chunk: int = 0        # resolved chunk length (scanned engine only)

    @property
    def us_per_round(self) -> float:
        """Mean wall time per algorithm round, EXCLUDING eval_fn time — so
        benchmark numbers measure round cost, not eval cadence."""
        return ((self.wall_time_s - self.eval_time_s)
                / max(self.rounds, 1) * 1e6)

    @property
    def final(self) -> Dict[str, Any]:
        return self.rows[-1] if self.rows else {}

    def column(self, key: str) -> List[Any]:
        return [r.get(key) for r in self.rows]


class _Recorder:
    """Row construction + eval bookkeeping shared by both engines, so the
    scanned path emits EXACTLY the eager path's rows."""

    def __init__(self, trace: Trace, alg, eval_fn, on_row, t0: float):
        self.trace, self.alg = trace, alg
        self.eval_fn, self.on_row, self.t0 = eval_fn, on_row, t0
        self.state = None          # kept current by the driving loop
        self.evaled_round = 0      # last round whose row carried an eval

    def run_eval(self, r: int):
        t_e = time.time()
        with jax.profiler.TraceAnnotation(EVAL):
            res = self.eval_fn(self.alg.eval_params(self.state))
        self.trace.eval_time_s += time.time() - t_e
        self.evaled_round = r
        return res if isinstance(res, dict) else {"eval": res}

    def record(self, r: int, metrics, bits_up, bits_down, do_eval: bool):
        with jax.profiler.TraceAnnotation(SYNC):
            row = dict(normalize_metrics(metrics), round=r,
                       bits_up_total=float(bits_up),
                       bits_down_total=float(bits_down),
                       wall_time_s=time.time() - self.t0)
        if do_eval and self.eval_fn is not None:
            row.update(self.run_eval(r))
        self.trace.rows.append(row)
        if self.on_row is not None:
            self.on_row(row)

    def finalize(self, r: int, metrics, bits_up, bits_down):
        """Backstop exit (unreachable budget / max_rounds): guarantee the
        final round has a (fully evaluated) row. If an eval-less row for
        the final round was already recorded (and streamed), update it in
        place so on_row never fires twice for one round."""
        rows = self.trace.rows
        if r and (not rows or rows[-1]["round"] != r):
            self.record(r, metrics, bits_up, bits_down, True)
        elif r and self.eval_fn is not None and self.evaled_round != r:
            rows[-1].update(self.run_eval(r))


def simulate(alg: FedAlgorithm, params0, data, key, *,
             rounds: Optional[int] = None,
             until_sim_time: Optional[float] = None,
             until_bits: Optional[float] = None,
             eval_every: int = 10,
             record_every: int = 0,
             eval_fn: Optional[Callable[[Any], Any]] = None,
             on_row: Optional[Callable[[Dict[str, Any]], None]] = None,
             name: str = "", max_rounds: int = 100_000,
             scan_chunk: Union[int, str] = 0) -> Trace:
    """Run ``alg`` from ``params0`` until the budget is exhausted.

    Budgets compose (first one hit wins): ``rounds`` server rounds,
    ``until_sim_time`` simulated seconds, ``until_bits`` total communication
    bits (up + down). At least one must be given; ``max_rounds`` is the
    backstop when a sim-time/bits budget is unreachable (e.g. an algorithm
    that never sends bits), and the final round is always recorded (and
    evaluated) even when the loop ends on the backstop. ``eval_fn(params)``
    is called every ``eval_every`` rounds (and on the final round); its
    result lands in the trace row. ``record_every`` records metrics-only
    rows on its own (usually denser) cadence WITHOUT paying for an eval —
    e.g. ``record_every=1, eval_every=0`` traces every round's
    ``h_zero_frac`` but evaluates only once, at the end. ``on_row`` streams
    every recorded row to the caller as it happens (progress logging).

    ``scan_chunk=K`` (K >= 2) selects the scanned engine for algorithms
    with the ``device_round`` capability: rounds execute in jitted
    ``lax.scan`` chunks of up to K rounds, one host sync per chunk (see the
    module docstring for the exact semantics). Prefer K dividing
    ``eval_every`` — each distinct chunk length compiles once.
    ``scan_chunk="auto"`` autotunes K before the run
    (:meth:`repro.fed.engine.RoundEngine.autotune`): each candidate length
    runs a compile+warmup chunk and one timed chunk on a disposable probe
    state, with a probe key folded OUT of the run's key schedule — the
    tuned run's round keys (and trace) are identical to passing the winning
    K explicitly. The resolved length lands in ``Trace.scan_chunk``.

    Eager path: device->host syncs happen only where a value is genuinely
    needed on the host — the stop condition of an active sim-time/bits
    budget, and row recording. A rounds-only budget leaves the device
    pipeline free to run ahead between recorded rows.
    """
    if rounds is None and until_sim_time is None and until_bits is None:
        raise ValueError("give at least one budget: rounds / until_sim_time "
                         "/ until_bits")
    if scan_chunk == "auto" and not supports_scan(alg):
        scan_chunk = 0       # autotune has nothing to tune: eager fallback
    if scan_chunk and (scan_chunk == "auto" or scan_chunk > 1) \
            and supports_scan(alg):
        return _simulate_scanned(
            alg, params0, data, key, rounds=rounds,
            until_sim_time=until_sim_time, until_bits=until_bits,
            eval_every=eval_every, record_every=record_every,
            eval_fn=eval_fn, on_row=on_row, name=name,
            max_rounds=max_rounds, scan_chunk=scan_chunk)
    trace = Trace(algorithm=name or type(alg).__name__)
    state = alg.init(params0)
    # cumulative counters accumulate device-side (no per-round sync)
    bits_up = bits_down = 0.0
    t0 = time.time()
    rec = _Recorder(trace, alg, eval_fn, on_row, t0)
    r = 0
    metrics = {}
    limit = min(rounds, max_rounds) if rounds is not None else max_rounds

    done = False
    while r < limit and not done:
        key, sub = jax.random.split(key)
        state, metrics = alg.round(state, data, sub)
        rec.state = state
        r += 1
        bits_up = bits_up + metrics.get("bits_up", 0.0)
        bits_down = bits_down + metrics.get("bits_down", 0.0)
        done = rounds is not None and r >= rounds
        if not done and until_sim_time is not None:
            with jax.profiler.TraceAnnotation(SYNC):
                done = float(metrics.get("sim_time", 0.0)) >= until_sim_time
        if not done and until_bits is not None:
            with jax.profiler.TraceAnnotation(SYNC):
                done = float(bits_up) + float(bits_down) >= until_bits
        do_eval = done or (eval_every and r % eval_every == 0)
        if do_eval or (record_every and r % record_every == 0):
            rec.record(r, metrics, bits_up, bits_down, do_eval)
    rec.state = state
    rec.finalize(r, metrics, bits_up, bits_down)
    trace.final_state = state
    trace.rounds = r
    trace.wall_time_s = time.time() - t0
    return trace


def _simulate_scanned(alg, params0, data, key, *, rounds, until_sim_time,
                      until_bits, eval_every, record_every, eval_fn, on_row,
                      name, max_rounds, scan_chunk) -> Trace:
    """The scanned engine: K-round jitted chunks, one host sync per chunk.

    Bit accumulation mirrors the eager path's on-device float32 adds
    (``np.float32`` partial sums), so ``bits_*_total`` rows match the eager
    engine exactly for device algorithms.
    """
    trace = Trace(algorithm=name or type(alg).__name__, engine="scanned")
    # the engine's compiled chunk programs are cached ON the algorithm (like
    # the eager path's jitted round), so repeated simulate() calls — warmup
    # then timed bench runs, compare() sweeps — never recompile
    engine = getattr(alg, "_round_engine", None)
    if engine is None or engine.alg is not alg:
        engine = RoundEngine(alg)
        try:
            alg._round_engine = engine
        except AttributeError:   # slotted/frozen algorithm: uncached
            pass
    limit = min(rounds, max_rounds) if rounds is not None else max_rounds
    if scan_chunk == "auto":
        # probe BEFORE the run state exists (one state generation live) and
        # with a key folded off the run's stream — the tuned run's round
        # keys are identical to passing the winning K explicitly
        cap = limit
        if eval_fn is not None and eval_every:
            cap = min(cap, eval_every)
        scan_chunk = engine.autotune(params0, data,
                                     jax.random.fold_in(key, 0x5EED),
                                     cap=cap)
    trace.scan_chunk = int(scan_chunk)
    state = alg.init(params0)
    bits_up = np.float32(0.0)
    bits_down = np.float32(0.0)
    t0 = time.time()
    rec = _Recorder(trace, alg, eval_fn, on_row, t0)
    r = 0
    metrics = {}

    done = False
    while r < limit and not done:
        n = limit - r
        if eval_fn is not None and eval_every:
            # shrink so eval rounds land on chunk boundaries, where the
            # state (hence eval_params) is materialized
            n = min(n, eval_every - (r % eval_every))
        n = min(n, scan_chunk)
        key, state, stacked = engine.run_chunk(state, data, key, n)
        rec.state = state
        with jax.profiler.TraceAnnotation(SYNC):
            host = jax.device_get(stacked)   # the chunk's single host sync
        for j in range(n):
            rj = r + j + 1
            mj = {k: v[j] for k, v in host.items()}
            bits_up = np.float32(bits_up + mj.get("bits_up", 0.0))
            bits_down = np.float32(bits_down + mj.get("bits_down", 0.0))
            done_j = rounds is not None and rj >= rounds
            at_boundary = j == n - 1
            # sim-time / bits budgets: checked at chunk boundaries only
            if not done_j and at_boundary and until_sim_time is not None:
                done_j = float(mj.get("sim_time", 0.0)) >= until_sim_time
            if not done_j and at_boundary and until_bits is not None:
                done_j = float(bits_up) + float(bits_down) >= until_bits
            do_eval = done_j or (eval_every and rj % eval_every == 0)
            if do_eval or (record_every and rj % record_every == 0):
                # eval only ever fires at a boundary (chunks are aligned)
                rec.record(rj, mj, bits_up, bits_down,
                           do_eval and at_boundary)
            done = done or done_j
            metrics = mj
        r += n
    rec.state = state
    rec.finalize(r, metrics, bits_up, bits_down)
    trace.final_state = state
    trace.rounds = r
    trace.wall_time_s = time.time() - t0
    return trace


def compare(algorithms: Dict[str, FedAlgorithm], params0, data, key,
            **sim_kw) -> Dict[str, Trace]:
    """Run every named algorithm from the SAME initial params, key stream,
    and budget — the paper's equal-clock / equal-bits comparison. Returns
    ``{name: Trace}`` in input order."""
    return {name: simulate(alg, params0, data, key, name=name, **sim_kw)
            for name, alg in algorithms.items()}
