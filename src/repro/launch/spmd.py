"""The mesh-sharded QuAFL train step behind the unified FedAlgorithm API.

Historically ``launch/train.py --algo spmd`` drove ``build_train_step``
through a bespoke loop with its own state and ad-hoc metrics — the one
execution path outside the protocol (ROADMAP: "SPMD path onto the unified
API"). :class:`SpmdAlgorithm` closes that gap: the distributed step
(clients living on mesh data slices, exchange running as mesh collectives)
becomes a registry algorithm (``make_algorithm("spmd", ..., cfg=...)``)
whose ``round`` emits the standardized metrics schema, so SPMD runs land in
the same ``simulate()`` Trace format as every simulator algorithm — and,
because the round is pure traced code over a pytree state, the scanned
engine (``simulate(..., scan_chunk=K)``) applies to distributed training
too.

Mapping notes:
  * one client per mesh slot — ``n_slots`` comes from the mesh (the 'data'
    axis, or 'pod' in cohort mode), NOT from ``fed.n_clients``; ``data``
    (the stacked per-client token pools from
    :func:`repro.data.synthetic.federated_token_task`) must provide at
    least ``n_slots`` clients and the first ``n_slots`` are used.
  * the clock observation is QuAFL's (the step IS Algorithm 1): every round
    lasts ``swt + sit`` simulated seconds; H_i is drawn inside the step.
  * bit accounting is QuAFL's: s quantized uplink messages plus ONE
    downlink broadcast Enc(X_t) per round (``tree_bits`` over the param
    tree), plus the transport's gathered side-channel / coded-re-gather
    payload (``Transport.extra_bits_down`` — the (n-1) extra γ/levels f32
    rows a code all-gather moves, or the scatter-resident coded
    redistribution of the fused reduce_scatter) charged into ``bits_down``.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.compression.codecs import IdentityCodec, resolve_codec
from repro.configs.base import FedConfig, ModelConfig, ShapeConfig
from repro.core.transport import tree_bits
from repro.launch.steps import (TrainState, build_train_step, fed_mode_for,
                                n_slots_for)
from repro.models.moe import MOE_COUNTERS
from repro.utils.spans import LOCAL_STEPS, ROUND


class SpmdState(NamedTuple):
    """Mesh train state + the clock/bit counters the schema requires."""
    train: TrainState
    sim_time: jnp.ndarray
    bits_up: jnp.ndarray
    bits_down: jnp.ndarray

    @property
    def bits_sent(self):
        return self.bits_up + self.bits_down


@dataclass(eq=False)
class SpmdAlgorithm:
    """Registry name ``"spmd"``. Requires ``cfg`` (the ModelConfig whose
    params pytree ``init``/``round`` operate on); ``mesh`` defaults to a
    single-device (1, 1) data×model mesh, which is the CPU-CI instance of
    the same program a pod runs via GSPMD."""
    fed: FedConfig
    template: Any                      # params pytree (shapes only)
    cfg: ModelConfig = None
    mesh: Any = None
    batch: int = 2                     # per-client microbatch rows
    seq: int = 32
    fed_mode: Optional[str] = None
    transport: Optional[str] = None
    remat: bool = False

    def __post_init__(self):
        if self.cfg is None:
            raise ValueError("SpmdAlgorithm needs cfg=<ModelConfig> (pass "
                             "it through make_algorithm('spmd', ..., "
                             "cfg=...))")
        if self.cfg.frontend:
            raise NotImplementedError("spmd registry path covers token-only "
                                      "architectures (no frontend batches)")
        if self.mesh is None:
            from repro.utils.compat import make_mesh
            self.mesh = make_mesh((1, 1), ("data", "model"))
        self.fed_mode = self.fed_mode or fed_mode_for(self.cfg.name)
        self.n_slots = n_slots_for(self.mesh, self.fed_mode)
        shape = ShapeConfig("spmd", self.seq, self.batch * self.n_slots,
                            "train")
        # per-direction codecs drive both the step build and the metrics'
        # wire accounting (bits computed BY the codec, per leaf)
        self.codec_up = resolve_codec(None, self.fed, direction="up",
                                      default="lattice")
        self.codec_down = resolve_codec(None, self.fed, direction="down",
                                        default="lattice")
        quantized = not (isinstance(self.codec_up, IdentityCodec)
                         and isinstance(self.codec_down, IdentityCodec))
        with self.mesh:
            self._step, _, (self._state_sh, _, _) = build_train_step(
                self.cfg, self.fed, self.mesh, shape,
                fed_mode=self.fed_mode, transport=self.transport,
                quantized=quantized, remat=self.remat)
        self._bits_up_msg = tree_bits(self.codec_up, self.template)
        self._bits_down_msg = tree_bits(self.codec_down, self.template)
        # the transport's redistribution payload (gathered γ/levels rows,
        # or the fused reduce_scatter's coded shard re-gather) is downlink
        # traffic the per-message codec math cannot see — charge it per
        # leaf at the mesh's slot count (0 on the (1,1) CI mesh)
        from repro.compression.transports import transport_for_mode
        tr = transport_for_mode(self.transport or self.fed.transport)
        self._extra_bits_down = 0
        if tr is not None and hasattr(tr, "extra_bits_down"):
            self._extra_bits_down = sum(
                tr.extra_bits_down(self.codec_up, self.codec_down,
                                   int(v.size), self.n_slots)
                for v in jax.tree_util.tree_leaves(self.template))

    # ------------------------------------------------------------------
    def init(self, params0) -> SpmdState:
        """The state, built by one program straight into the build
        shardings: GSPMD lays the clients out along the mesh data axis and
        each device materializes only its own slice (an eager broadcast
        would first hold all n_slots copies on one device). The outputs
        are fresh buffers, never views of ``params0`` (the eager round
        donates its input state). The counters are placed too: the round
        returns them mesh-replicated, and an unplaced first input would
        make the second round compile again."""
        def build(params):
            train = TrainState(
                server={k: jnp.array(v) for k, v in params.items()},
                clients={k: jnp.broadcast_to(v[None],
                                             (self.n_slots,) + v.shape)
                         for k, v in params.items()},
                t=jnp.zeros((), jnp.int32))
            return SpmdState(train=train, sim_time=jnp.zeros(()),
                             bits_up=jnp.zeros(()), bits_down=jnp.zeros(()))

        repl = self._state_sh.t
        shardings = SpmdState(train=self._state_sh, sim_time=repl,
                              bits_up=repl, bits_down=repl)
        return jax.jit(build, out_shardings=shardings)(params0)

    def device_round(self, state: SpmdState, data, key):
        """One mesh round: sample each slot's (K, b) microbatches from its
        token pool, run the distributed step, standardize the metrics."""
        fed = self.fed
        n, K = self.n_slots, fed.local_steps
        k_b, k_r = jax.random.split(key)
        pool = data["tokens"].shape[1]
        with jax.named_scope(LOCAL_STEPS):
            idx = jax.random.randint(k_b, (n, K, self.batch), 0, pool)
            toks = jax.vmap(lambda p, ix: p[ix])(data["tokens"][:n], idx)
        train, m = self._step(state.train, {"tokens": toks},
                              jax.random.key_data(k_r))

        # QuAFL bit accounting: s uplink messages, one downlink broadcast,
        # plus the transport's gathered side-channel rows / coded re-gather
        bits_up = jnp.asarray(n * self._bits_up_msg, jnp.float32)
        bits_down = jnp.asarray(self._bits_down_msg
                                + self._extra_bits_down, jnp.float32)
        dt = fed.swt + fed.sit
        new_time = state.sim_time + dt
        # schema quant_err: RMS decode error relative to the server norm
        # (the step measures the squared error summed over leaves)
        srv_sq = sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                     for v in train.server.values())
        rel = jnp.sqrt(m["quant_err_sq"]) / (jnp.sqrt(srv_sq) + 1e-12)
        metrics = {
            "sim_time": new_time,
            "round_time": jnp.asarray(dt, jnp.float32),
            "bits_up": bits_up,
            "bits_down": bits_down,
            "h_steps_mean": m["h_steps_mean"],
            "quant_err": rel,
            "quant_err_sq": m["quant_err_sq"],
            # MoE models: the rows of each local step, summed over slots
            **{k: m[k] for k in MOE_COUNTERS if k in m},
        }
        return SpmdState(train=train, sim_time=new_time,
                         bits_up=state.bits_up + bits_up,
                         bits_down=state.bits_down + bits_down), metrics

    # the eager round donates the incoming state (the legacy driver loop's
    # donate_argnums, folded into the protocol entry point); the scanned
    # engine drives device_round instead, where scan carries the buffers
    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _round(self, state: SpmdState, data, key):
        return self.device_round(state, data, key)

    def round(self, state: SpmdState, data, key):
        with jax.profiler.TraceAnnotation(ROUND):
            return self._round(state, data, key)

    def eval_params(self, state: SpmdState):
        return state.train.server
