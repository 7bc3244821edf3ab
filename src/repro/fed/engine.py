"""Device-resident round engine: jit/scan-able rounds for every algorithm.

The paper's headline claim is *simultaneous* support for data heterogeneity,
partial asynchrony, and compression at up-to-300-node scale — which requires
the per-round host overhead (python loop, per-round dispatch, metric syncs)
to vanish from the hot path. This module is the single home for that
machinery:

  * **``device_round`` capability** — an algorithm that exposes
    ``device_round(state, data, key) -> (state, metrics)`` as PURE traced
    code (state a pytree, metrics a dict of device scalars with a fixed
    structure, no ``float()``/``int()``/host control flow) can be run in
    K-round ``lax.scan`` chunks with a single host sync per chunk.
    :class:`DeviceFedAlgorithm` is the structural type;
    :func:`supports_scan` is the capability check. Algorithms whose control
    NEEDS the host (e.g. the adaptive bit-width walk, which selects a jit
    cache by python-int bits) can instead provide
    ``scan_rounds(state, data, key, length)`` and manage their own chunking.

  * **:class:`RoundEngine`** — compiles and caches one scanned chunk
    program per chunk length. The scan body splits the key exactly like the
    eager ``simulate()`` loop (``key, sub = split(key)`` per round), so a
    scanned run reproduces the eager run under the same seed — bit-for-bit
    in the equivalence suite, up to float32 rounding for kernels XLA fuses
    differently inside a multi-round loop body.

  * **:class:`RingBuffer`** — a fixed-capacity, device-resident event queue
    (times + client ids, empty slots at ``+inf``) replacing the python
    min-heap ``repro.fed.clock.ArrivalQueue``. ``ring_pop`` is a masked-min
    with the heap's lexicographic ``(time, client)`` tie-break, so the pop
    order is pinned bit-for-bit against the heap over any event stream
    (property test in ``tests/test_engine.py``).

  * **seed bridge** — :func:`fedbuff_completion_table` replays the legacy
    numpy event stream host-side into a ``(client, occurrence) -> duration``
    table, so the device-resident FedBuff can consume the EXACT draws of the
    python implementation and be pinned bit-for-bit against it.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed.api import FedAlgorithm
from repro.utils.spans import CHUNK


@runtime_checkable
class DeviceFedAlgorithm(FedAlgorithm, Protocol):
    """A :class:`FedAlgorithm` whose round is pure traced code.

    ``device_round`` must be side-effect free and jit/scan-able: the state a
    registered pytree, every metric a device scalar, the metrics dict
    structure identical every round. ``round`` may simply alias (a jitted)
    ``device_round``.
    """

    def device_round(self, state, data, key) -> Tuple[Any, Dict[str, Any]]:
        ...


def supports_scan(alg) -> bool:
    """True if ``alg`` can run scanned chunks — either via the generic
    ``device_round`` capability or its own ``scan_rounds`` implementation."""
    return (callable(getattr(alg, "device_round", None))
            or callable(getattr(alg, "scan_rounds", None)))


# ---------------------------------------------------------------------------
# fixed-capacity device event queue (replaces clock.ArrivalQueue's heap)
# ---------------------------------------------------------------------------

class RingBuffer(NamedTuple):
    """Fixed-capacity (time, client) event set. Empty slots hold
    ``times=+inf`` / ``clients=-1`` so the masked-min pop skips them."""
    times: jnp.ndarray    # (cap,) float32
    clients: jnp.ndarray  # (cap,) int32

    @property
    def capacity(self) -> int:
        return self.times.shape[0]


def ring_init(capacity: int) -> RingBuffer:
    return RingBuffer(times=jnp.full((capacity,), jnp.inf, jnp.float32),
                      clients=jnp.full((capacity,), -1, jnp.int32))


def ring_size(rb: RingBuffer) -> jnp.ndarray:
    return jnp.sum(jnp.isfinite(rb.times).astype(jnp.int32))


def ring_push(rb: RingBuffer, t, client) -> RingBuffer:
    """Insert into the first empty slot. The caller must not push into a
    full buffer (the FedBuff formulation holds exactly one pending event per
    client, so capacity = n_clients is never exceeded)."""
    slot = jnp.argmax(~jnp.isfinite(rb.times))
    return RingBuffer(
        times=rb.times.at[slot].set(jnp.asarray(t, jnp.float32)),
        clients=rb.clients.at[slot].set(jnp.asarray(client, jnp.int32)))


def ring_peek(rb: RingBuffer) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(time, client) of the next event — the heap's lexicographic min:
    smallest time, ties broken by smallest client id (then first slot)."""
    t_min = jnp.min(rb.times)
    big = jnp.iinfo(jnp.int32).max
    cand = jnp.where(rb.times == t_min, rb.clients, big)
    c_min = jnp.min(cand)
    return t_min, c_min


def ring_pop(rb: RingBuffer) -> Tuple[RingBuffer, jnp.ndarray, jnp.ndarray]:
    """Remove and return the lexicographic-min event. Masked-min formulation
    of ``heapq.heappop`` on ``(time, client)`` tuples — pinned bit-for-bit
    against :class:`repro.fed.clock.ArrivalQueue` in the tests."""
    t_min, c_min = ring_peek(rb)
    slot = jnp.argmax((rb.times == t_min) & (rb.clients == c_min))
    out = RingBuffer(times=rb.times.at[slot].set(jnp.inf),
                     clients=rb.clients.at[slot].set(-1))
    return out, t_min, c_min


# ---------------------------------------------------------------------------
# seed bridge: legacy numpy event stream -> device-consumable draw table
# ---------------------------------------------------------------------------

def fedbuff_completion_table(key, lam, local_steps: int,
                             n_events: int) -> np.ndarray:
    """Replay the legacy ``(np.random.Generator, ArrivalQueue)`` event
    stream host-side and return ``table[i, k]`` = the duration drawn for
    client ``i``'s ``k``-th completion (float32, ``(n, n_events + 1)``).

    The replay consumes the numpy rng in EXACTLY the legacy order — n
    initial draws (clients 0..n-1), then one redraw per pop, in pop order —
    so a device-resident FedBuff indexing ``table[i, occ_i]`` sees the same
    durations as the python implementation seeded from the same ``key``
    (the rng seed derivation matches ``FedBuff._seed``).
    """
    from repro.fed.clock import ArrivalQueue, completion_time
    rng = np.random.default_rng(
        int(jax.random.randint(key, (), 0, 2 ** 31 - 1)))
    n = len(lam)
    table = np.zeros((n, n_events + 1), np.float32)
    occ = np.zeros(n, np.int64)
    q = ArrivalQueue()
    for i in range(n):
        d = completion_time(rng, local_steps, lam[i])
        table[i, 0] = d
        occ[i] = 1
        q.push(d, i)
    for _ in range(n_events):
        t_now, i = q.pop()
        d = completion_time(rng, local_steps, lam[i])
        if occ[i] >= table.shape[1]:   # one client absorbed every event
            table = np.pad(table, ((0, 0), (0, n_events)))
        table[i, occ[i]] = d
        occ[i] += 1
        q.push(t_now + d, i)
    return table


# ---------------------------------------------------------------------------
# the engine: cached scanned-chunk programs
# ---------------------------------------------------------------------------

# chunk lengths the autotuner probes (each costs one compile + two runs)
AUTOTUNE_CANDIDATES = (4, 16, 64)


class RoundEngine:
    """Runs an algorithm's rounds as jitted ``lax.scan`` chunks.

    One compiled program per distinct chunk length (cached); the stacked
    per-round metrics come back as ONE device value, so a chunk costs a
    single host sync instead of one per round. The key-split schedule inside
    the scan body is identical to the eager ``simulate()`` loop, making
    scanned runs bit-for-bit reproductions of eager runs.

    :meth:`autotune` picks the chunk length empirically —
    ``simulate(..., scan_chunk="auto")`` exposes it.
    """

    def __init__(self, alg):
        if not supports_scan(alg):
            raise TypeError(
                f"{type(alg).__name__} exposes neither device_round nor "
                "scan_rounds; run it through the eager simulate() path")
        self.alg = alg
        self._chunk_fns: Dict[int, Any] = {}
        self.tuned_chunk: int | None = None

    def autotune(self, params0, data, key, cap: int = 0,
                 candidates=AUTOTUNE_CANDIDATES) -> int:
        """Pick a chunk length from measured us_per_round of 2-chunk probes.

        Each candidate length runs TWO chunks on a disposable
        ``alg.init(params0)`` state: the first pays the compile + warmup,
        the second is timed. The probe state is donated through the chain,
        so peak memory stays one state generation; the probe ``key`` should
        be derived OUT of the caller's key schedule (``simulate`` folds one
        off) so tuning never perturbs the run's round keys. ``cap > 0``
        bounds the candidates (e.g. to ``eval_every`` so chunks don't
        straddle eval points). The winner is cached on the engine — compiled
        chunk programs for the winning length are reused by the real run.
        """
        if self.tuned_chunk is not None:
            return self.tuned_chunk
        import time
        cands = sorted({min(c, cap) if cap else c
                        for c in candidates if c >= 2})
        if not cands:
            cands = [2]
        best, best_us = cands[0], float("inf")
        state = self.alg.init(params0)
        for c in cands:
            key, state, ms = self.run_chunk(state, data, key, c)  # warmup
            jax.block_until_ready(ms)
            t0 = time.perf_counter()
            key, state, ms = self.run_chunk(state, data, key, c)
            jax.block_until_ready(ms)
            us = (time.perf_counter() - t0) / c * 1e6
            if us < best_us:
                best, best_us = c, us
        self.tuned_chunk = best
        return best

    def _chunk_program(self, length: int):
        """The python chunk body for ``length`` rounds — the ONE closure
        shared by :meth:`run_chunk` (jitted + donated), :meth:`traced_chunk`
        (jaxpr for the analyzers), and :meth:`lowered_chunk` (compiled
        executable for the donation audit)."""
        round_fn = self.alg.device_round

        def run(state, data, key):
            def body(carry, _):
                k, st = carry
                k, sub = jax.random.split(k)
                st, m = round_fn(st, data, sub)
                return (k, st), m

            (k, st), ms = jax.lax.scan(body, (key, state), None,
                                       length=length)
            return k, st, ms

        return run

    def _commit_carry(self, state, key):
        """Normalize the carry of a MESH-sharded state and return
        ``(state, key, out_shardings)``; single-device states pass through
        with ``out_shardings=None``.

        Two-part contract for mesh states (spmd): every unplaced leaf (host
        scalars from ``init``, the caller's host-made key) is placed
        replicated on the state's mesh, and the chunk outputs are pinned to
        the input shardings. Without both, the carry is not a fixed point:
        GSPMD repicks output layouts freely (a physical reshard of every
        leaf per chunk on a real mesh) and the first call's
        uncommitted-leaf signature differs from every later one — a silent
        recompile per run, which the recompile sentinel flags.

        Single-device states are left alone: their carry signature is
        already stable, and pinning ``out_shardings`` there would itself
        split the jit cache on the first call's uncommitted inputs."""
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = None
        for leaf in jax.tree_util.tree_leaves(state):
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding):
                mesh = sh.mesh
                break
        if mesh is None:
            return state, key, None
        repl = NamedSharding(mesh, PartitionSpec())

        def place(leaf):
            if (hasattr(leaf, "sharding")
                    and not isinstance(leaf.sharding, NamedSharding)):
                return jax.device_put(leaf, repl)
            return leaf

        state = jax.tree_util.tree_map(place, state)
        key = jax.device_put(key, repl)
        state_sh = jax.tree_util.tree_map(lambda l: l.sharding, state)
        return state, key, (key.sharding, state_sh, None)

    def chunk_fn(self, length: int, carry_out=None):
        """The cached jitted chunk program for ``length`` (compiling it on
        first use, with the carry outputs pinned to ``carry_out`` when
        given). Exposed so the recompile sentinel can interrogate the jit
        cache (``fn._cache_size()``) after a run."""
        fn = self._chunk_fns.get(length)
        if fn is None:
            kw = {}
            if carry_out is not None:
                kw["out_shardings"] = carry_out
            fn = jax.jit(self._chunk_program(length), donate_argnums=(0,),
                         **kw)
            self._chunk_fns[length] = fn
        return fn

    def run_chunk(self, state, data, key, length: int):
        """Advance ``length`` rounds on device.

        Returns ``(key, state, stacked_metrics)`` where ``stacked_metrics``
        leaves carry a leading ``(length,)`` axis (round-major).

        The INPUT ``state`` buffers are DONATED to the compiled chunk: for
        d=2^20+ regimes the scan carry reuses the caller's state
        allocation instead of holding both generations live across the
        chunk entry (ROADMAP scan-polish item a). Callers must treat the
        passed-in state as consumed — both ``simulate()`` and the adaptive
        walk already discard it in favour of the returned state. The
        (tiny, caller-supplied) ``key`` is NOT donated.
        """
        with jax.profiler.TraceAnnotation(CHUNK):
            custom = getattr(self.alg, "scan_rounds", None)
            if custom is not None:
                return custom(state, data, key, length)
            state, key, carry_out = self._commit_carry(state, key)
            return self.chunk_fn(length, carry_out)(state, data, key)

    # -- analyzer hooks (repro.analysis) ------------------------------------

    def traced_round(self, state, data, key):
        """Closed jaxpr of ONE round — ``device_round`` exactly as the scan
        body calls it. Tracing is abstract: no device work, no state
        consumed."""
        return jax.make_jaxpr(
            lambda st, d, k: self.alg.device_round(st, d, k)
        )(state, data, key)

    def traced_chunk(self, state, data, key, length: int):
        """Closed jaxpr of the ``length``-round scanned chunk program (the
        same closure :meth:`run_chunk` jits, including the per-round
        ``key, sub = split(key)`` schedule)."""
        return jax.make_jaxpr(self._chunk_program(length))(state, data, key)

    def wire_provenance(self, state, data, key):
        """Message/collective provenance of one traced round, for the
        wire-truth audit: ``(closed, marks, collectives)`` where marks are
        the ``wire_mark`` sites (params, aval, path) and collectives the
        ``(prim, [(aval, taint), ...], path)`` facts from the taint flow.
        Analysis imports stay lazy — tracing never pays for them unless a
        caller asks for provenance."""
        from repro.analysis.wire import collect_wire_facts
        closed = self.traced_round(state, data, key)
        marks, colls = collect_wire_facts(closed)
        return closed, marks, colls

    def lowered_chunk(self, state, data, key, length: int):
        """The chunk program lowered with the donation contract of
        :meth:`run_chunk` (``donate_argnums=(0,)``) — ``.compile()`` it to
        audit the executable's input-output aliasing. Deliberately NOT the
        cached run fn: auditing must not warm (or be confused by) the run
        cache."""
        return jax.jit(self._chunk_program(length),
                       donate_argnums=(0,)).lower(state, data, key)
