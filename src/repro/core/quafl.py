"""QuAFL — paper Algorithm 1, as a jit-able JAX round function.

The optimization state is kept as FLAT fp32 vectors (the paper's model is
x ∈ R^d): ``server`` (X_t) plus a :class:`repro.fed.population.Population`
store holding every per-client row — X^i models stacked (n, d), speeds λ,
last-interaction times, codec/EF residuals. Rounds reach the store only
through an O(s·d) gather/scatter of the sampled clients' rows, and WHO is
sampled is a first-class ``Participation`` spec (``uniform`` — the paper's
draw — ``gamma_straggler``, ``cyclic:period=P,phase_groups=G``), so the
population size n is a spec, not a hot-path cost. The loss is evaluated by
unflattening against a template pytree, so any model (the MLP family from
the paper's experiments or a transformer from the assigned zoo) plugs in
through ``loss_fn(params_pytree, batch)``.

Faithfulness notes:
 * Per App. B.1, local steps of unsampled clients have no observable effect,
   so they are computed lazily at poll time: on contact, client i draws
   H_i^t = min(K, Poisson(λ_i · elapsed_i)) — the number of Exp(λ_i)-duration
   steps it would have completed since its last interaction — and replays
   exactly that many SGD steps (masked lax.scan). H may be 0: the client is
   polled mid-flight with no progress, and still participates (paper §2.2).
   The speed model and the lazy draw live in ``repro.fed.clock`` (shared
   with every baseline so the comparison runs under ONE clock).
 * η_i = H_min/H_i dampening uses the EXPECTED speeds (weighted variant);
   the unweighted variant (paper App. A experiments) sets η_i = 1.
 * Both directions are quantized with the position-aware lattice quantizer.
   The server's Enc(X_t) is decoded by each sampled client against its own
   current model; the clients' Enc(Y^i) are decoded by the server against
   X_t (pseudocode lines 4–7).
 * Averaging: X_{t+1} = (X_t + Σ Q(Y^i)) / (s+1);
   X^i ← Q(X_t)/(s+1) + s·Y^i/(s+1) — preserves the model mean μ_t up to
   gradient and quantization noise (the paper's potential argument).

Perf: with lattice-family codecs both ways (the default) the whole exchange
runs through the rotated-space compression pipeline
(repro.compression.pipeline): one shared per-round rotation key, all
encode/decode/averaging in rotated coordinates,
exactly s+1 forward + s+1 inverse full-model rotations per round (the seed
composition spent ~5s+1; the downlink Enc(X_t) is an elementwise quantize of
the cached rotated server). ``FedConfig.kernel_backend`` selects the
jnp / Pallas-interpret / Pallas implementation of the fused kernels;
``exchange_impl="reference"`` keeps the per-message materialize-everything
oracle for equivalence testing.

This class implements the :class:`repro.fed.FedAlgorithm` protocol
(``init / round / eval_params``) and emits the standardized metrics schema
(``sim_time``, ``bits_up``, ``bits_down``, ``h_steps_mean``, ``quant_err``,
...); select it by name via ``repro.fed.make_algorithm("quafl", ...)``.

Compression is COMPOSABLE (:mod:`repro.compression.codecs`): ``uplink=`` /
``downlink=`` codec specs (or ``FedConfig.codec_up`` / ``codec_down``)
select the per-direction scheme by name — lattice-family codecs (including
sub-byte ``lattice_packed`` wires and per-client heterogeneous
``{"fast": ..., "slow": ...}`` bit budgets) keep riding the fused
rotated-space pipeline; any other codec runs the per-message composition.
``bits_up`` / ``bits_down`` are computed by the codecs' wire accounting.
Error-feedback residuals assume a ZERO decode reference, which QuAFL's
model-vs-server uplink does not provide — stateful codecs therefore run
their stateless encode here (``QuaflState.codec_up_state`` stays empty
unless a codec declares itself reference-agnostic); the delta-style
uplinks (``fedbuff``, ``compressed_fedavg``) are where EF threads.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression.codecs import (GroupedLatticeCodec,
                                      init_client_states, is_lattice_family,
                                      resolve_codec)
from repro.compression.pipeline import ExchangePipeline
from repro.configs.base import FedConfig
# canonical home is repro.fed.clock; re-exported here for compatibility
from repro.fed.clock import (client_speeds, expected_steps,  # noqa: F401
                             lazy_h_steps, sample_clients, speeds_for)
from repro.fed.population import (Population, build_population, gather_rows,
                                  resolve_participation, scatter_rows,
                                  shard_population, with_rows)
from repro.utils.spans import EXCHANGE, LOCAL_STEPS, POPULATION
from repro.utils.tree import (tree_flatten_vector, tree_unflatten_vector)


class QuaflState(NamedTuple):
    """Server scalars + the :class:`Population` store of per-client rows.

    Per-client state (client models X^i, last-interaction times, codec/EF
    residuals, speeds) lives as stacked rows of ``pop``; rounds touch it
    only through an O(s·d) gather/scatter of the s sampled clients' rows,
    so the state layout scales to populations of 10^5+ clients. The legacy
    field names stay available as read-only views."""
    server: jnp.ndarray        # X_t  (d,)
    pop: Population            # rows: model (n,d), last_time (n,), lam,
    #                          # group, codec_up (EF state or ())
    t: jnp.ndarray             # server round
    sim_time: jnp.ndarray      # simulated wall-clock
    bits_up: jnp.ndarray       # cumulative client->server bits
    bits_down: jnp.ndarray     # cumulative server->client bits
    srv_dist_est: jnp.ndarray  # running ‖X_t − X^i‖ estimate (server Enc hint)

    @property
    def clients(self):
        """X^i stacked (n, d) — view into the population store."""
        return self.pop.rows["model"]

    @property
    def last_time(self):
        """(n,) last interaction time per client — view into the store."""
        return self.pop.rows["last_time"]

    @property
    def codec_up_state(self):
        """Per-client uplink-codec (EF) state; () for stateless codecs."""
        return self.pop.rows["codec_up"]

    def with_clients(self, clients) -> QuaflState:
        """Copy with the stacked client models replaced (test helper —
        the NamedTuple ``_replace`` can't target rows inside ``pop``)."""
        return self._replace(pop=with_rows(self.pop, model=clients))

    @property
    def bits_sent(self):
        """Total communication bits, both directions (legacy accessor)."""
        return self.bits_up + self.bits_down


@dataclass(eq=False)
class QuAFL:
    fed: FedConfig
    loss_fn: Callable[[Any, Any], Any]     # (params_pytree, batch) -> (loss, m)
    template: Any                          # params pytree template
    batch_fn: Callable[[Any, jax.Array], Any]  # (client_data, key) -> batch
    avg_mode: str = "both"                 # 'both'|'server_only'|'client_only'
    uniform_speeds: bool = False
    exchange_impl: str = "pipeline"        # 'pipeline' | 'reference' (oracle)
    uplink: Any = None                     # codec spec (default: lattice)
    downlink: Any = None                   # codec spec (default: lattice)
    participation: Any = None              # spec (default: fed.participation)
    client_mesh: Any = None                # shard the store's client axis

    def __post_init__(self):
        backend = getattr(self.fed, "kernel_backend", "jnp")
        n = self.fed.n_clients
        self.lam = speeds_for(self.fed, n, uniform=self.uniform_speeds)
        # per-direction codecs; the straggler mask resolves group specs
        # ({"fast": ..., "slow": ...}) into per-client bit budgets
        slow_mask = np.asarray(self.lam) == np.float32(self.fed.lam_slow)
        self.codec_up = resolve_codec(self.uplink, self.fed, direction="up",
                                      default="lattice", slow_mask=slow_mask)
        self.codec_down = resolve_codec(self.downlink, self.fed,
                                        direction="down", default="lattice")
        # rotated-space exchange engine whenever BOTH directions are
        # lattice-family (QSGD/identity/top-k have no rotation to
        # restructure around); shares every knob with the codecs so bit
        # accounting and γ derivation stay in lockstep
        self.pipeline = (ExchangePipeline(bits=self.codec_up.bits,
                                          block=self.codec_up.block,
                                          safety=self.codec_up.safety,
                                          backend=backend)
                         if (is_lattice_family(self.codec_up)
                             and is_lattice_family(self.codec_down))
                         else None)
        self.H = expected_steps(self.fed, self.lam)
        self.eta_i = ((self.H.min() / self.H) if self.fed.weighted
                      else np.ones(n)).astype(np.float32)
        # hoisted once — the traced round body only indexes these
        self._lam_j = jnp.asarray(self.lam)
        self._eta_j = jnp.asarray(self.eta_i)
        # who enters a round is a first-class spec on the clock
        self.part = resolve_participation(self.participation, self.fed)
        self.d = int(sum(np.prod(x.shape) for x in
                         jax.tree_util.tree_leaves(self.template)))

    # ------------------------------------------------------------------
    @property
    def _thread_ef(self) -> bool:
        """QuAFL's uplink is decoded against the SERVER model (non-zero
        reference), so error-feedback residuals — which assume the decoder
        reconstructs zero off the transmitted support — are only threaded
        for codecs that declare themselves reference-agnostic; everything
        else uses the stateless encode."""
        return self.codec_up.stateful and not getattr(
            self.codec_up, "ef_zero_ref_only", True)

    def _codec_state0(self):
        return (init_client_states(self.codec_up, self.fed.n_clients,
                                   self.d) if self._thread_ef else ())

    def init(self, params0) -> QuaflState:
        x0 = tree_flatten_vector(params0)
        n = self.fed.n_clients
        pop = build_population(self.fed, n, lam=self.lam,
                               model=jnp.tile(x0[None], (n, 1)),
                               last_time=jnp.zeros((n,)),
                               codec_up=self._codec_state0())
        if self.client_mesh is not None:
            pop = shard_population(pop, self.client_mesh)
        return QuaflState(
            server=x0, pop=pop,
            t=jnp.zeros((), jnp.int32), sim_time=jnp.zeros(()),
            bits_up=jnp.zeros(()), bits_down=jnp.zeros(()),
            srv_dist_est=jnp.ones(()) * 1e-3)

    # ------------------------------------------------------------------
    def _grad(self, flat, batch):
        def f(v):
            loss, _ = self.loss_fn(tree_unflatten_vector(self.template, v),
                                   batch)
            return loss
        return jax.grad(f)(flat)

    def _local_progress(self, flat, data_i, h_steps, key):
        """Replay up to K masked SGD steps; returns h̃ (sum of step grads)."""
        K, eta = self.fed.local_steps, self.fed.lr

        def step(carry, q):
            x, h = carry
            g = self._grad(x, self.batch_fn(data_i, jax.random.fold_in(key, q)))
            act = (q < h_steps).astype(jnp.float32)
            return (x - eta * act * g, h + act * g), None

        (_, h), _ = jax.lax.scan(step, (flat, jnp.zeros_like(flat)),
                                 jnp.arange(K))
        return h

    # ------------------------------------------------------------------
    @partial(jax.jit, static_argnums=0)
    def round(self, state: QuaflState, data, key):
        """One server round. data: stacked per-client datasets (n, ...)."""
        fed = self.fed
        n, s = fed.n_clients, fed.s
        k_sel, k_h, k_q, k_loc = jax.random.split(key, 4)

        # participation spec on the clock: who answers this round's poll.
        # Everything below touches the population only through the sampled
        # rows — O(s·d), independent of n.
        with jax.named_scope(POPULATION):
            idx = self.part.sample(k_sel, state.t, n, s, state.pop.rows["lam"])
            got = gather_rows(state.pop, idx)
            data_s = jax.tree_util.tree_map(lambda a: a[idx], data)
        cl = got["model"]                                        # (s, d)
        with jax.named_scope(LOCAL_STEPS):
            elapsed = state.sim_time + fed.swt + fed.sit - got["last_time"]
            h_steps = self.part.h_steps(k_h, idx, got["lam"], elapsed,
                                        fed.local_steps)
            keys = jax.random.split(k_loc, s)
            h_tilde = jax.vmap(self._local_progress)(cl, data_s, h_steps,
                                                     keys)
        with jax.named_scope(EXCHANGE):
            eta_i = self._eta_j[idx][:, None]
            prog = fed.lr * eta_i * h_tilde                      # η·η_i·h̃
            Y = cl - prog                                        # (s, d)

            # --- quantized exchange (shared per-interaction keys) -------
            prog_norm = jnp.linalg.norm(prog, axis=1)
            hints_up = prog_norm + state.srv_dist_est + 1e-8
            cs_new = None          # sampled clients' updated EF rows (if any)

            if self.pipeline is not None:
                # rotated-space engine: one shared rotation per round, all
                # encode/decode/averaging in rotated coordinates (s+1 forward,
                # s+1 inverse full-model rotations — audited in the tests).
                # The per-direction codecs parameterize the wire (bit-width,
                # sub-byte packing, per-client levels) without touching the
                # rotation structure.
                fn = (self.pipeline.quafl_round
                      if self.exchange_impl == "pipeline"
                      else self.pipeline.quafl_round_reference)
                server_new, cl_new, hint_srv, rel_err = fn(
                    k_q, state.server, Y, hints_up, avg_mode=self.avg_mode,
                    up=self.codec_up.wire(idx), down=self.codec_down.wire())
            else:
                # scalar / identity / top-k: no rotation to restructure around
                kq_cl = jax.random.split(jax.random.fold_in(k_q, 1), s)

                if self._thread_ef:
                    cs = got["codec_up"]            # gathered EF rows (s, ...)

                    def enc_dec_up(y, kk, hint, cs_i):
                        msg, cs_i = self.codec_up.encode_stateful(
                            kk, y, hint, cs_i)
                        return (self.codec_up.decode(kk, msg, state.server),
                                cs_i)

                    QY, cs_new = jax.vmap(enc_dec_up)(Y, kq_cl, hints_up, cs)
                else:
                    def enc_dec_up(y, kk, hint):
                        msg = self.codec_up.encode(kk, y, hint)
                        return self.codec_up.decode(kk, msg, state.server)

                    QY = jax.vmap(enc_dec_up)(Y, kq_cl, hints_up)    # (s, d)

                # server -> clients: ONE encode, per-client decode vs own X^i
                kq_srv = jax.random.fold_in(k_q, 0)
                hint_srv = (jnp.max(jnp.linalg.norm(QY - state.server[None],
                                                    axis=1)) + 1e-8)
                msg_srv = self.codec_down.encode(kq_srv, state.server,
                                                 hint_srv)
                QX = jax.vmap(
                    lambda ref: self.codec_down.decode(kq_srv, msg_srv,
                                                       ref))(cl)

                # --- averaging --------------------------------------------
                if self.avg_mode == "both":
                    server_new = (state.server + jnp.sum(QY, 0)) / (s + 1)
                    cl_new = QX / (s + 1) + s * Y / (s + 1)
                elif self.avg_mode == "server_only":
                    server_new = (state.server + jnp.sum(QY, 0)) / (s + 1)
                    cl_new = QX
                elif self.avg_mode == "client_only":
                    server_new = jnp.mean(QY, 0)
                    cl_new = QX / (s + 1) + s * Y / (s + 1)
                else:  # 'none' — plain replacement both sides
                    server_new = jnp.mean(QY, 0)
                    cl_new = QX
                rel_err = jnp.mean(jnp.linalg.norm(QY - Y, axis=1)
                                   / (jnp.linalg.norm(Y, axis=1) + 1e-9))

        # bit accounting, computed BY the codecs' wire formats: s uplink
        # messages (per-client widths under a grouped codec) + ONE downlink
        # broadcast Enc(X_t) (every sampled client decodes the same codes
        # against its own model)
        if isinstance(self.codec_up, GroupedLatticeCodec):
            bits_up = self.codec_up.bits_for(idx, self.d)   # traced sum
        else:
            bits_up = s * self.codec_up.message_bits(self.d)
        bits_down = self.codec_down.message_bits(self.d)
        dt = fed.swt + fed.sit
        new_time = state.sim_time + dt
        # scatter the s updated rows back into the store (O(s·d); untouched
        # rows pass through by reference so the scan carry stays donatable)
        updates = {"model": cl_new, "last_time": new_time}
        if cs_new is not None:
            updates["codec_up"] = cs_new
        with jax.named_scope(POPULATION):
            pop = scatter_rows(state.pop, idx, updates)
        state = QuaflState(
            server=server_new, pop=pop,
            t=state.t + 1, sim_time=new_time,
            bits_up=state.bits_up + bits_up,
            bits_down=state.bits_down + bits_down,
            srv_dist_est=0.5 * state.srv_dist_est + 0.5 * hint_srv)
        metrics = {
            "sim_time": new_time,
            "round_time": jnp.asarray(dt, jnp.float32),
            "bits_up": jnp.asarray(bits_up, jnp.float32),
            "bits_down": jnp.asarray(bits_down, jnp.float32),
            "h_steps_mean": jnp.mean(h_steps.astype(jnp.float32)),
            "h_zero_frac": jnp.mean((h_steps == 0).astype(jnp.float32)),
            "quant_err": rel_err,
            "bits": jnp.asarray(bits_up + bits_down, jnp.float32),
        }
        return state, metrics

    # ------------------------------------------------------------------
    def device_round(self, state: QuaflState, data, key):
        """Device-resident round capability (:mod:`repro.fed.engine`): the
        round body is pure traced code — state a pytree, metrics device
        scalars — so the engine can ``lax.scan`` it in K-round chunks."""
        return self.round(state, data, key)

    def eval_params(self, state: QuaflState):
        return tree_unflatten_vector(self.template, state.server)

    def mean_model(self, state: QuaflState):
        mu = (state.server + jnp.sum(state.clients, 0)) / (self.fed.n_clients + 1)
        return tree_unflatten_vector(self.template, mu)
