"""Synchronous FedAvg baselines (paper App. A.2 + compressed variants).

:class:`FedAvg` — paper App. A.2 'FedAvg' specification: each round the
server sends its model to s random clients; each performs EXACTLY K local
steps and returns the result; the server averages. The server must wait for
the SLOWEST sampled client: simulated round time = max_i Gamma(K, λ_i) +
sit (swt = 0 in FedAvg). The speed model and the straggler draw come from
``repro.fed.clock`` — the same clock every algorithm in the comparison runs
under. Registry name ``"fedavg"``.

Codecs: FedAvg defaults to ``identity`` both ways (the paper's
uncompressed baseline, bit-for-bit the historical implementation), but any
:mod:`repro.compression.codecs` spec plugs in per direction — uplink
messages are the client models decoded against the server (position-aware
reference), the downlink distortion is a broadcast Enc(X_t) each sampled
client decodes before starting its local steps.

:class:`CompressedFedAvg` — registry name ``"compressed_fedavg"``: the
FedPAQ / compressed-FedAvg family (arXiv:2106.07155; controlled averaging
with compression, arXiv:2308.08165) built PURELY from the codec API as
composition proof. Clients upload codec-compressed model DELTAS (decoded
against the zero vector — the sound reference for every codec, including
non-position-aware ``scalar``), the server applies the averaged decoded
delta with a server learning rate, and the downlink is ONE broadcast
Enc(X_t) decoded against the previous round's server model. Stateful
codecs (``topk_ef``) get their per-client error-feedback residuals
threaded through the state.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression.codecs import (IdentityCodec, init_client_states,
                                      resolve_codec)
from repro.configs.base import FedConfig
from repro.fed.clock import (sample_clients, speeds_for,  # noqa: F401
                             straggler_round_time)
from repro.fed.population import (Population, build_population,
                                  resolve_participation, scatter_rows,
                                  shard_population)
from repro.utils.tree import tree_flatten_vector, tree_unflatten_vector


class FedAvgState(NamedTuple):
    server: jnp.ndarray
    pop: Population            # per-client rows: lam, group
    t: jnp.ndarray
    sim_time: jnp.ndarray
    bits_up: jnp.ndarray
    bits_down: jnp.ndarray

    @property
    def bits_sent(self):
        """Total communication bits, both directions (legacy accessor)."""
        return self.bits_up + self.bits_down


@dataclass(eq=False)
class FedAvg:
    fed: FedConfig
    loss_fn: Callable[[Any, Any], Any]
    template: Any
    batch_fn: Callable[[Any, jax.Array], Any]
    uniform_speeds: bool = False
    uplink: Any = None                  # codec spec (default: identity)
    downlink: Any = None                # codec spec (default: identity)
    participation: Any = None           # spec (default: fed.participation)
    client_mesh: Any = None             # shard the store's client axis
    # subclasses override the per-direction codec defaults
    _codec_default_up = "identity"
    _codec_default_down = "identity"

    def __post_init__(self):
        n = self.fed.n_clients
        self.lam = speeds_for(self.fed, n, uniform=self.uniform_speeds)
        self.part = resolve_participation(self.participation, self.fed)
        self.d = int(sum(np.prod(x.shape) for x in
                         jax.tree_util.tree_leaves(self.template)))
        self.codec_up = resolve_codec(self.uplink, self.fed, direction="up",
                                      default=self._codec_default_up)
        self.codec_down = resolve_codec(self.downlink, self.fed,
                                        direction="down",
                                        default=self._codec_default_down)
        self._up_identity = isinstance(self.codec_up, IdentityCodec)
        self._down_identity = isinstance(self.codec_down, IdentityCodec)
        # stateful codecs degrade gracefully to their stateless encode here
        # (fedavg clients keep no cross-round memory); compressed_fedavg
        # threads real per-client error-feedback residuals

    def _pop0(self, **extra_rows) -> Population:
        pop = build_population(self.fed, self.fed.n_clients, lam=self.lam,
                               **extra_rows)
        if self.client_mesh is not None:
            pop = shard_population(pop, self.client_mesh)
        return pop

    def init(self, params0) -> FedAvgState:
        return FedAvgState(server=tree_flatten_vector(params0),
                           pop=self._pop0(),
                           t=jnp.zeros((), jnp.int32),
                           sim_time=jnp.zeros(()), bits_up=jnp.zeros(()),
                           bits_down=jnp.zeros(()))

    def _grad(self, flat, batch):
        def f(v):
            loss, _ = self.loss_fn(tree_unflatten_vector(self.template, v),
                                   batch)
            return loss
        return jax.grad(f)(flat)

    def _local(self, start, data_i, kk):
        """EXACTLY K local SGD steps from ``start``."""
        K = self.fed.local_steps

        def step(x, q):
            g = self._grad(x, self.batch_fn(data_i,
                                            jax.random.fold_in(kk, q)))
            return x - self.fed.lr * g, None

        x, _ = jax.lax.scan(step, start, jnp.arange(K))
        return x

    @partial(jax.jit, static_argnums=0)
    def round(self, state: FedAvgState, data, key):
        fed = self.fed
        n, s, K = fed.n_clients, fed.s, fed.local_steps
        k_sel, k_loc, k_t = jax.random.split(key, 3)
        # codec keys derive via fold_in so the legacy (identity/identity)
        # key schedule — and hence the PR 3 trace — is untouched
        k_q = jax.random.fold_in(key, 17)
        idx = self.part.sample(k_sel, state.t, n, s, state.pop.rows["lam"])
        data_s = jax.tree_util.tree_map(lambda a: a[idx], data)
        keys = jax.random.split(k_loc, s)

        # downlink: ONE broadcast Enc(X_t); every sampled client decodes it
        # against the server reference before stepping. The identity pair
        # skips the codec calls entirely — the uncompressed baseline keeps
        # the paper's round cost (no extra O(s·d) norm reductions)
        if self._down_identity:
            start = state.server
        else:
            k_dn = jax.random.fold_in(k_q, 0)
            msg_dn = self.codec_down.encode(k_dn, state.server,
                                            jnp.asarray(1e-8, jnp.float32))
            start = self.codec_down.decode(k_dn, msg_dn, state.server)

        models = jax.vmap(lambda di, kk: self._local(start, di, kk))(
            data_s, keys)

        # uplink: client models decoded against the server (position-aware
        # reference)
        if self._up_identity:
            QY = models
            rel_err = jnp.zeros(())
        else:
            kq_cl = jax.random.split(jax.random.fold_in(k_q, 1), s)
            hints = jnp.linalg.norm(models - state.server[None],
                                    axis=1) + 1e-8

            def enc_dec(x, kk, hint):
                return self.codec_up.decode(
                    kk, self.codec_up.encode(kk, x, hint), state.server)

            QY = jax.vmap(enc_dec)(models, kq_cl, hints)
            rel_err = jnp.mean(jnp.linalg.norm(QY - models, axis=1)
                               / (jnp.linalg.norm(models, axis=1) + 1e-9))
        server_new = jnp.mean(QY, 0)
        # slowest sampled client: sum of K Exp(λ) step times
        dt = straggler_round_time(k_t, state.pop.rows["lam"][idx], K,
                                  fed.sit)
        # wire accounting by the codecs: s unicasts each way
        bits_up = s * self.codec_up.message_bits(self.d)
        bits_down = s * self.codec_down.message_bits(self.d)
        metrics = {
            "sim_time": state.sim_time + dt,
            "round_time": dt,
            "bits_up": jnp.asarray(bits_up, jnp.float32),
            "bits_down": jnp.asarray(bits_down, jnp.float32),
            "h_steps_mean": jnp.asarray(K, jnp.float32),  # exactly K, always
            "quant_err": rel_err,
            "bits": jnp.asarray(bits_up + bits_down, jnp.float32),
        }
        return FedAvgState(server=server_new, pop=state.pop, t=state.t + 1,
                           sim_time=state.sim_time + dt,
                           bits_up=state.bits_up + bits_up,
                           bits_down=state.bits_down + bits_down), metrics

    def device_round(self, state: FedAvgState, data, key):
        """Device-resident round capability (:mod:`repro.fed.engine`)."""
        return self.round(state, data, key)

    def eval_params(self, state):
        return tree_unflatten_vector(self.template, state.server)


# ---------------------------------------------------------------------------
# compressed FedAvg (FedPAQ family) — registry name "compressed_fedavg"
# ---------------------------------------------------------------------------

class CompressedFedAvgState(NamedTuple):
    server: jnp.ndarray
    pop: Population            # rows: lam, group, codec_up (EF residuals)
    t: jnp.ndarray
    sim_time: jnp.ndarray
    bits_up: jnp.ndarray
    bits_down: jnp.ndarray
    srv_prev: jnp.ndarray      # previous server model (downlink decode ref)
    srv_dist_est: jnp.ndarray  # running ‖X_t − X_{t-1}‖ (downlink Enc hint)

    @property
    def codec_up_state(self):
        """Per-client error-feedback residuals — a population row."""
        return self.pop.rows["codec_up"]

    @property
    def bits_sent(self):
        return self.bits_up + self.bits_down


@dataclass(eq=False)
class CompressedFedAvg(FedAvg):
    """Compressed synchronous FedAvg, composed purely from the codec API.

    Uplink: per-client model deltas, codec-encoded with hint ‖Δ‖ and
    decoded against the ZERO vector (sound for position-aware and scalar
    codecs alike — exactly FedPAQ when ``uplink="scalar"``). Downlink: one
    broadcast Enc(X_t) decoded against the previous server model (every
    client received that broadcast last round). Defaults: uplink
    ``lattice`` at ``fed.bits``, downlink ``identity``.
    """
    server_lr: float = 1.0
    # uplink defaults to lattice, downlink to the uncompressed broadcast;
    # downlink stateful codecs degrade to their stateless encode (one
    # broadcast encoder; only uplink residuals are threaded)
    _codec_default_up = "lattice"
    _codec_default_down = "identity"

    def _codec_state0(self):
        return init_client_states(self.codec_up, self.fed.n_clients,
                                  self.d)

    def init(self, params0) -> CompressedFedAvgState:
        x0 = tree_flatten_vector(params0)
        return CompressedFedAvgState(
            server=x0, pop=self._pop0(codec_up=self._codec_state0()),
            t=jnp.zeros((), jnp.int32), sim_time=jnp.zeros(()),
            bits_up=jnp.zeros(()), bits_down=jnp.zeros(()),
            # a COPY: server and srv_prev must never alias (the scanned
            # engine donates the state, and XLA rejects donating one
            # buffer twice)
            srv_prev=jnp.array(x0), srv_dist_est=jnp.ones(()) * 1e-3)

    @partial(jax.jit, static_argnums=0)
    def round(self, state: CompressedFedAvgState, data, key):
        fed = self.fed
        n, s, K = fed.n_clients, fed.s, fed.local_steps
        k_sel, k_loc, k_t = jax.random.split(key, 3)
        k_q = jax.random.fold_in(key, 17)
        idx = self.part.sample(k_sel, state.t, n, s, state.pop.rows["lam"])
        data_s = jax.tree_util.tree_map(lambda a: a[idx], data)
        keys = jax.random.split(k_loc, s)

        # downlink broadcast: Enc(X_t) decoded against X_{t-1}
        k_dn = jax.random.fold_in(k_q, 0)
        msg_dn = self.codec_down.encode(k_dn, state.server,
                                        state.srv_dist_est + 1e-8)
        start = self.codec_down.decode(k_dn, msg_dn, state.srv_prev)

        models = jax.vmap(lambda di, kk: self._local(start, di, kk))(
            data_s, keys)
        deltas = start[None] - models                     # descent direction

        # uplink: codec-compressed deltas decoded against zero
        kq_cl = jax.random.split(jax.random.fold_in(k_q, 1), s)
        hints = jnp.linalg.norm(deltas, axis=1) + 1e-12
        zero = jnp.zeros((self.d,), jnp.float32)
        pop_new = state.pop

        if self.codec_up.stateful:
            cs = jax.tree_util.tree_map(lambda a: a[idx],
                                        state.codec_up_state)

            def enc_dec(dl, kk, hint, cs_i):
                msg, cs_i = self.codec_up.encode_stateful(kk, dl, hint, cs_i)
                return self.codec_up.decode(kk, msg, zero), cs_i

            QD, cs_new = jax.vmap(enc_dec)(deltas, kq_cl, hints, cs)
            # scatter the sampled clients' EF residuals back (O(s·d))
            pop_new = scatter_rows(state.pop, idx, {"codec_up": cs_new})
        else:
            def enc_dec(dl, kk, hint):
                return self.codec_up.decode(
                    kk, self.codec_up.encode(kk, dl, hint), zero)

            QD = jax.vmap(enc_dec)(deltas, kq_cl, hints)

        server_new = state.server - self.server_lr * jnp.mean(QD, 0)
        rel_err = jnp.mean(jnp.linalg.norm(QD - deltas, axis=1)
                           / (jnp.linalg.norm(deltas, axis=1) + 1e-12))
        dt = straggler_round_time(k_t, state.pop.rows["lam"][idx], K,
                                  fed.sit)
        bits_up = s * self.codec_up.message_bits(self.d)
        bits_down = self.codec_down.message_bits(self.d)  # ONE broadcast
        new_time = state.sim_time + dt
        new_state = CompressedFedAvgState(
            server=server_new, pop=pop_new, t=state.t + 1,
            sim_time=new_time,
            bits_up=state.bits_up + bits_up,
            bits_down=state.bits_down + bits_down,
            srv_prev=state.server,
            srv_dist_est=0.5 * state.srv_dist_est
            + 0.5 * jnp.linalg.norm(server_new - state.server))
        metrics = {
            "sim_time": new_time,
            "round_time": dt,
            "bits_up": jnp.asarray(bits_up, jnp.float32),
            "bits_down": jnp.asarray(bits_down, jnp.float32),
            "h_steps_mean": jnp.asarray(K, jnp.float32),
            "quant_err": rel_err,
            "bits": jnp.asarray(bits_up + bits_down, jnp.float32),
        }
        return new_state, metrics

    def device_round(self, state: CompressedFedAvgState, data, key):
        """Device-resident round capability (:mod:`repro.fed.engine`)."""
        return self.round(state, data, key)
