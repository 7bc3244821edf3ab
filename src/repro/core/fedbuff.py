"""FedBuff baseline [Nguyen et al., 30]: buffered asynchronous aggregation.

Clients run continuously; when client i finishes its K local steps (duration
Gamma(K, λ_i)) it ships the model DELTA to a shared buffer and restarts from
the current server model. Once the buffer holds Z updates the server applies
the averaged delta. ``uplink=`` / ``downlink=`` codec specs (or
``FedConfig.codec_up`` / ``codec_down``) select ANY registered codec per
direction; both default to ``identity`` (fp32). Two uplinks matter here:

  * ``uplink="scalar"``  — the paper's Fig. 6/16 QSGD variant. FedBuff
    cannot lattice-quantize *models* (the server has no decoding key for a
    client's stale base model)…
  * ``uplink="lattice"`` — …but the DELTA is position-aware decodable
    against the zero vector with hint ‖Δ‖, so delta compression rides the
    same fused rotate+quantize pipeline as QuAFL (backend selected by
    ``FedConfig.kernel_backend``). Beyond-paper option.

A stateful uplink codec (``topk_ef``) gets its per-client error-feedback
residuals threaded through ``FedBuffState.ef`` on this python
implementation.

FedBuff's control flow is data-dependent, so it is simulated (event-driven
python around a jitted local-steps function) rather than SPMD. The event
machinery — ``Gamma(K, λ)`` completion times feeding a min-heap of arrivals —
lives in ``repro.fed.clock`` (the same clock every baseline runs under).

The class implements the :class:`repro.fed.FedAlgorithm` protocol: ``round``
advances the event simulation until ONE buffer flush (one server update) and
returns the standardized metrics. The state is a python-side record (not a
jax pytree) — rounds are deterministic given ``init`` plus the FIRST round
key, which seeds the event rng exactly like the legacy ``run`` entry point;
later round keys are ignored. ``run`` is a thin wrapper over the same
single-completion step: the event order, rng stream, and model iterates are
identical to the legacy loop. The history's bits column now counts BOTH
directions (each restart downloads the fp32 server model, d·32 bits, on top
of the uplink delta) — the legacy loop counted the uplink only.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression.codecs import IdentityCodec, resolve_codec
from repro.configs.base import FedConfig
from repro.fed.clock import (ArrivalQueue, completion_time,
                             completion_time_device, speeds_for)
from repro.fed.engine import RingBuffer, ring_init, ring_pop, ring_push
from repro.fed.population import (Population, build_population,
                                  shard_population, with_rows)
from repro.utils.tree import tree_flatten_vector, tree_unflatten_vector


def _copy_rng(rng: np.random.Generator) -> np.random.Generator:
    new = np.random.default_rng()
    new.bit_generator.state = rng.bit_generator.state
    return new


@dataclass
class FedBuffState:
    """Event-driven simulation state (python-side; NOT a jax pytree)."""
    server: jnp.ndarray
    start_model: List[jnp.ndarray]      # model each client started from
    queue: Optional[ArrivalQueue]       # pending completion events
    buffer: List[jnp.ndarray]           # deltas awaiting the next flush
    sim_time: float = 0.0
    t: int = 0                          # server updates applied
    bits_up: float = 0.0
    bits_down: float = 0.0
    rng: Optional[np.random.Generator] = None   # seeded on first round
    jkey: Optional[jax.Array] = None
    ef: Optional[List] = None           # per-client residuals of a stateful
    #                                   # (error-feedback) uplink codec

    @property
    def bits_sent(self):
        """Total communication bits, both directions (legacy accessor)."""
        return self.bits_up + self.bits_down


@dataclass(eq=False)
class FedBuff:
    fed: FedConfig
    loss_fn: Callable[[Any, Any], Any]
    template: Any
    batch_fn: Callable[[Any, jax.Array], Any]
    buffer_size: int = 10
    server_lr: float = 1.0
    uniform_speeds: bool = False
    uplink: Any = None        # codec spec for the deltas (default identity;
    #                         # 'scalar' is the paper's, 'lattice' decodes
    #                         # the delta against zero)
    downlink: Any = None      # codec spec for the restart broadcast
    #                         # (default identity: fp32 server model)

    def __post_init__(self):
        n = self.fed.n_clients
        self.lam = speeds_for(self.fed, n, uniform=self.uniform_speeds)
        self.codec_up = resolve_codec(self.uplink, self.fed, direction="up",
                                      default="identity")
        self.codec_down = resolve_codec(self.downlink, self.fed,
                                        direction="down",
                                        default="identity")
        self._down_identity = isinstance(self.codec_down, IdentityCodec)
        self._up_compressed = not isinstance(self.codec_up, IdentityCodec)
        self.d = int(sum(np.prod(x.shape) for x in
                         jax.tree_util.tree_leaves(self.template)))

        @partial(jax.jit)
        def _local(server_flat, data_i, key):
            def f(v, batch):
                loss, _ = self.loss_fn(
                    tree_unflatten_vector(self.template, v), batch)
                return loss

            def step(x, q):
                g = jax.grad(f)(x, self.batch_fn(
                    data_i, jax.random.fold_in(key, q)))
                return x - self.fed.lr * g, None

            x, _ = jax.lax.scan(step, server_flat,
                                jnp.arange(self.fed.local_steps))
            return server_flat - x  # delta (positive direction of descent)

        self._local = _local

    # ------------------------------------------------------------------
    # FedAlgorithm protocol
    # ------------------------------------------------------------------
    def init(self, params0) -> FedBuffState:
        server = tree_flatten_vector(params0)
        n = self.fed.n_clients
        ef = ([self.codec_up.init_state(self.d) for _ in range(n)]
              if self.codec_up.stateful else None)
        return FedBuffState(server=server,
                            start_model=[server for _ in range(n)],
                            queue=None, buffer=[], ef=ef)

    def _seed(self, state: FedBuffState, key) -> FedBuffState:
        """Seed the event rng from a jax key (legacy ``run`` derivation)."""
        rng = np.random.default_rng(
            int(jax.random.randint(key, (), 0, 2**31 - 1)))
        queue = ArrivalQueue.initial(rng, self.lam, self.fed.local_steps)
        return replace(state, rng=rng, queue=queue, jkey=key)

    @staticmethod
    def _fork(state: FedBuffState) -> FedBuffState:
        """Copy the mutable containers so the caller's state stays usable.

        Called ONCE per protocol ``round`` (not per completion event):
        ``_completion`` mutates in place, so a round of Z buffered arrivals
        costs one O(n_clients) copy instead of Z."""
        return replace(state, queue=state.queue.copy(),
                       start_model=list(state.start_model),
                       buffer=list(state.buffer), rng=_copy_rng(state.rng),
                       ef=None if state.ef is None else list(state.ef))

    def _completion(self, state: FedBuffState, data, want_metrics=False):
        """Process ONE client completion event, MUTATING ``state``.
        With ``want_metrics`` returns the relative quantization error of
        this delta as a DEVICE scalar (else/uncompressed: None) — the
        legacy ``run`` path skips the two extra full-model norms entirely,
        matching the work the original loop did."""
        t_now, i = state.queue.pop()
        state.jkey, sub = jax.random.split(state.jkey)
        delta = self._local(state.start_model[i], jax.tree_util.tree_map(
            lambda a: a[i], data), sub)
        rel_err = None
        if self._up_compressed:
            state.jkey, qk = jax.random.split(state.jkey)
            # deltas are decodable against the zero vector with hint ‖Δ‖
            # for every codec (position-aware lattice rides one fused
            # encode + decode pass; scalar/top-k ignore the reference);
            # stateful codecs thread the client's error-feedback residual
            hint = jnp.linalg.norm(delta) + 1e-12
            if self.codec_up.stateful:
                msg, state.ef[i] = self.codec_up.encode_stateful(
                    qk, delta, hint, state.ef[i])
            else:
                msg = self.codec_up.encode(qk, delta, hint)
            dq = self.codec_up.decode(qk, msg, jnp.zeros_like(delta))
            if want_metrics:
                rel_err = (jnp.linalg.norm(dq - delta)
                           / (jnp.linalg.norm(delta) + 1e-12))
            delta = dq
        state.bits_up += self.codec_up.message_bits(self.d)
        state.buffer.append(delta)
        if len(state.buffer) >= self.buffer_size:
            # Δ = start − end = η·Σg points downhill: w ← w − η_g·avg(Δ)
            state.server = state.server - self.server_lr * jnp.mean(
                jnp.stack(state.buffer), 0)
            state.buffer = []
            state.t += 1
        # client restarts from the downlinked server model: fp32 by
        # default, codec-encoded (decoded against the client's previous
        # start model — the reference it still holds) otherwise
        if self._down_identity:
            state.start_model[i] = state.server
        else:
            state.jkey, dk = jax.random.split(state.jkey)
            hint_dn = (jnp.linalg.norm(state.server - state.start_model[i])
                       + 1e-12)
            msg_dn = self.codec_down.encode(dk, state.server, hint_dn)
            state.start_model[i] = self.codec_down.decode(
                dk, msg_dn, state.start_model[i])
        state.bits_down += self.codec_down.message_bits(self.d)
        state.sim_time = float(t_now)
        state.queue.push(t_now + completion_time(
            state.rng, self.fed.local_steps, self.lam[i]), i)
        return rel_err

    def round(self, state: FedBuffState, data, key):
        """Advance the event simulation until ONE buffer flush (one server
        update). ``key`` seeds the rng on the first call only — the event
        stream is a single sequence, exactly as in the legacy ``run``. The
        input state is forked, not mutated."""
        if state.rng is None:
            state = self._seed(state, key)
        state = self._fork(state)
        t_before, errs = state.t, []
        time_before, up_before, down_before = (state.sim_time, state.bits_up,
                                               state.bits_down)
        while state.t == t_before:
            rel = self._completion(state, data, want_metrics=True)
            if rel is not None:
                errs.append(rel)
        metrics = {
            "sim_time": state.sim_time,
            "round_time": state.sim_time - time_before,
            "bits_up": state.bits_up - up_before,
            "bits_down": state.bits_down - down_before,
            # every buffered arrival carries exactly K completed steps
            "h_steps_mean": float(self.fed.local_steps),
            "quant_err": float(jnp.mean(jnp.stack(errs))) if errs else 0.0,
            "buffer_flushes": 1.0,
        }
        return state, metrics

    def eval_params(self, state: FedBuffState):
        return tree_unflatten_vector(self.template, state.server)

    # ------------------------------------------------------------------
    # legacy entry point (exact event/eval ordering of the original loop)
    # ------------------------------------------------------------------
    def run(self, params0, data, key, total_time: float, eval_every: float,
            eval_fn):
        """Simulate until ``total_time``; returns list of (time, metrics,
        bits). Bit-identical event stream to the protocol ``round`` path —
        both drive the same single-completion step in the same order."""
        state = self._seed(self.init(params0), key)
        history, next_eval = [], 0.0
        while len(state.queue):
            t_now, _ = state.queue.peek()
            if t_now > total_time:
                break
            while t_now >= next_eval:
                history.append((next_eval, eval_fn(self.eval_params(state)),
                                state.bits_sent))
                next_eval += eval_every
            self._completion(state, data)   # run() owns state: no fork
        while next_eval <= total_time:
            history.append((next_eval, eval_fn(self.eval_params(state)),
                            state.bits_sent))
            next_eval += eval_every
        return history


# ---------------------------------------------------------------------------
# device-resident formulation (jit/scan-able; registry name fedbuff_device)
# ---------------------------------------------------------------------------

class FedBuffDeviceState(NamedTuple):
    """Pure-pytree FedBuff state: the python heap becomes a fixed-capacity
    :class:`repro.fed.engine.RingBuffer` (one pending completion per client,
    so capacity = n_clients and the buffer is always exactly full). The
    per-client rows — restart models, draw counters, speeds — live in the
    :class:`Population` store; events touch single rows (O(d)), so the
    population size only sets memory, not per-event cost."""
    server: jnp.ndarray        # (d,)
    pop: Population            # rows: start (n,d), occ (n,) i32, lam, group
    queue: RingBuffer          # pending completion events
    sim_time: jnp.ndarray      # f32 scalar
    t: jnp.ndarray             # i32 server updates applied
    bits_up: jnp.ndarray       # f32 scalar
    bits_down: jnp.ndarray     # f32 scalar
    jkey: jax.Array            # event key stream (local steps + quantize)
    live: jnp.ndarray          # bool: queue/jkey seeded by the first round

    @property
    def start(self):
        """(n, d) model each client restarted from — a population row."""
        return self.pop.rows["start"]

    @property
    def occ(self):
        """(n,) per-client completion-draw counters — a population row."""
        return self.pop.rows["occ"]

    @property
    def bits_sent(self):
        """Total communication bits, both directions (legacy accessor)."""
        return self.bits_up + self.bits_down


@dataclass(eq=False)
class FedBuffDevice(FedBuff):
    """Buffered asynchronous aggregation as PURE traced code.

    Semantically the same event simulation as :class:`FedBuff` — pop the
    earliest completion, compute the client's K-step delta, buffer it, flush
    every ``buffer_size`` arrivals, reschedule the client — but the state is
    a registered pytree and ``round`` is a single jit/scan-able program
    (``lax.scan`` over the Z completions of one flush; masked-min pop on the
    device ring buffer). This is what lets FedBuff join the scanned
    ``simulate()`` fast path and the SPMD engine (ROADMAP: "FedBuff protocol
    state, jit-able").

    Randomness: per-completion model/quantizer keys follow the legacy
    ``jkey`` split schedule exactly. Completion DURATIONS come from
    ``jax.random.gamma`` (device stream) by default — same distribution,
    different draws than the legacy numpy rng. Passing ``completion_table``
    (built by :func:`repro.fed.engine.fedbuff_completion_table` from the
    same seed — the "seed bridge") makes the device algorithm consume the
    EXACT legacy draws, pinning it bit-for-bit against :class:`FedBuff`
    (equivalence test in ``tests/test_engine.py``).

    Key semantics match the python class: the FIRST ``round`` key seeds the
    event stream (model-step, quantizer, and duration randomness all derive
    from the carried ``jkey``/table from then on) and later round keys are
    ignored — vary the seeding key, not later keys, to get an independent
    event stream. Determinism given ``init`` + the first key holds, and a
    scanned run is bit-for-bit the eager run.
    """
    completion_table: Optional[np.ndarray] = None
    client_mesh: Any = None             # shard the store's client axis

    def __post_init__(self):
        super().__post_init__()
        # stateful codecs degrade to their stateless encode here; the
        # python 'fedbuff' threads real per-client error feedback
        self._lam_j = jnp.asarray(self.lam)
        self._table_j = (jnp.asarray(self.completion_table, jnp.float32)
                         if self.completion_table is not None else None)

    # ------------------------------------------------------------------
    def init(self, params0) -> FedBuffDeviceState:
        server = tree_flatten_vector(params0)
        n = self.fed.n_clients
        pop = build_population(self.fed, n, lam=self.lam,
                               start=jnp.tile(server[None], (n, 1)),
                               occ=jnp.zeros((n,), jnp.int32))
        if self.client_mesh is not None:
            pop = shard_population(pop, self.client_mesh)
        return FedBuffDeviceState(
            server=server, pop=pop, queue=ring_init(n),
            sim_time=jnp.zeros(()), t=jnp.zeros((), jnp.int32),
            bits_up=jnp.zeros(()), bits_down=jnp.zeros(()),
            jkey=jax.random.PRNGKey(0), live=jnp.zeros((), bool))

    def _duration(self, kt, i, occ_i, lam_i):
        """Client i's next K-step duration: seed-bridge table lookup when
        pinned, else a device Gamma(K, 1/λ_i) draw. A table exhausted
        mid-simulation (more completions than the bridge replayed) poisons
        the clock with NaN instead of silently clamping the gather — an
        un-pinned event stream must be loud, not approximately right."""
        if self._table_j is not None:
            return jnp.where(occ_i < self._table_j.shape[1],
                             self._table_j[i, occ_i], jnp.nan)
        return completion_time_device(kt, self.fed.local_steps, lam_i)

    def _seeded(self, state: FedBuffDeviceState, key):
        """First-round seeding: initial completion draws for every client
        (table column 0 under the bridge, device draws otherwise)."""
        n = self.fed.n_clients
        if self._table_j is not None:
            times = self._table_j[:, 0]
        else:
            kts = jax.random.split(jax.random.fold_in(key, 0), n)
            times = jax.vmap(completion_time_device,
                             in_axes=(0, None, 0))(
                kts, self.fed.local_steps, state.pop.rows["lam"])
        queue = RingBuffer(times=times.astype(jnp.float32),
                           clients=jnp.arange(n, dtype=jnp.int32))
        return queue, jnp.ones((n,), jnp.int32), key

    # ------------------------------------------------------------------
    def device_round(self, state: FedBuffDeviceState, data, key):
        """One server update (one buffer flush) = a scan over exactly
        ``buffer_size`` completion events, fully on device."""
        fed = self.fed
        Z, d = self.buffer_size, self.d
        lam_row = state.pop.rows["lam"]
        queue, occ, jkey = jax.lax.cond(
            state.live,
            lambda: (state.queue, state.occ, state.jkey),
            lambda: self._seeded(state, key))

        def completion(carry, z):
            queue, occ, jkey, server, start, t_last, buffer, errs = carry
            queue, t_now, i = ring_pop(queue)
            jkey, sub = jax.random.split(jkey)
            delta = self._local(start[i], jax.tree_util.tree_map(
                lambda a: a[i], data), sub)
            rel = jnp.zeros(())
            if self._up_compressed:
                jkey, qk = jax.random.split(jkey)
                msg = self.codec_up.encode(
                    qk, delta, jnp.linalg.norm(delta) + 1e-12)
                dq = self.codec_up.decode(qk, msg, jnp.zeros_like(delta))
                rel = (jnp.linalg.norm(dq - delta)
                       / (jnp.linalg.norm(delta) + 1e-12))
                delta = dq
            buffer = buffer.at[z].set(delta)
            errs = errs.at[z].set(rel)
            # the buffer starts empty every protocol round, so the flush
            # lands on the Z-th completion — same mean-of-stack as legacy
            server = jax.lax.cond(
                z == Z - 1,
                lambda s: s - self.server_lr * jnp.mean(buffer, 0),
                lambda s: s, server)
            if self._down_identity:
                restart = server
            else:
                jkey, dk = jax.random.split(jkey)
                hint_dn = jnp.linalg.norm(server - start[i]) + 1e-12
                msg_dn = self.codec_down.encode(dk, server, hint_dn)
                restart = self.codec_down.decode(dk, msg_dn, start[i])
            start = start.at[i].set(restart)
            if self._table_j is None:
                jkey, kt = jax.random.split(jkey)
            else:
                kt = jkey   # bridge mode consumes no extra key (numpy rng
            #               # drew the durations in the legacy stream)
            dur = self._duration(kt, i, occ[i], lam_row[i])
            occ = occ.at[i].add(1)
            queue = ring_push(queue, t_now + dur, i)
            return (queue, occ, jkey, server, start, t_now, buffer,
                    errs), None

        carry0 = (queue, occ, jkey, state.server, state.start,
                  state.sim_time, jnp.zeros((Z, d)), jnp.zeros((Z,)))
        (queue, occ, jkey, server, start, t_now, _, errs), _ = jax.lax.scan(
            completion, carry0, jnp.arange(Z))

        # wire accounting by the per-direction codecs
        bits_up = jnp.asarray(Z * self.codec_up.message_bits(d), jnp.float32)
        bits_down = jnp.asarray(Z * self.codec_down.message_bits(d),
                                jnp.float32)
        new_time = t_now.astype(jnp.float32)
        new_state = FedBuffDeviceState(
            server=server,
            pop=with_rows(state.pop, start=start, occ=occ),
            queue=queue, sim_time=new_time, t=state.t + 1,
            bits_up=state.bits_up + bits_up,
            bits_down=state.bits_down + bits_down,
            jkey=jkey, live=jnp.ones((), bool))
        metrics = {
            "sim_time": new_time,
            "round_time": new_time - state.sim_time,
            "bits_up": bits_up,
            "bits_down": bits_down,
            "h_steps_mean": jnp.asarray(fed.local_steps, jnp.float32),
            "quant_err": (jnp.mean(errs) if self._up_compressed
                          else jnp.zeros(())),
            "buffer_flushes": jnp.ones(()),
        }
        return new_state, metrics

    @partial(jax.jit, static_argnums=0)
    def round(self, state: FedBuffDeviceState, data, key):
        return self.device_round(state, data, key)

    def eval_params(self, state: FedBuffDeviceState):
        return tree_unflatten_vector(self.template, state.server)

    # the legacy event loop belongs to the python implementation only
    run = None
