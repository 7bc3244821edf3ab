"""Config dataclasses: architecture, shapes, mesh, federation.

Every assigned architecture gets one file in this package with a ``config()``
(full, exact assigned numbers) and a ``reduced()`` (<=2 layers, d_model<=512,
<=4 experts) used by the CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Layer schedule
# ---------------------------------------------------------------------------

# attention kinds
ATTN_FULL = "full"
ATTN_SLIDING = "sliding"
ATTN_CHUNKED = "chunked"   # llama4-style local chunked attention
ATTN_MLA = "mla"           # deepseek multi-head latent attention
KIND_ATTN = "attn"
KIND_MAMBA = "mamba"


@dataclass(frozen=True)
class LayerSpec:
    """One layer inside the repeating period of the network."""
    kind: str = KIND_ATTN          # 'attn' | 'mamba'
    attn: str = ATTN_FULL          # attention flavour (if kind == 'attn')
    window: int = 0                # sliding-window / chunk size (0 = n/a)
    mlp: str = "dense"             # 'dense' | 'moe'
    use_rope: bool = True          # NoPE layers (llama4 global) set False
    rope_theta: float = 0.0        # per-layer override (0 = model default)


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    d_ff_shared: int = 0           # defaults to d_ff_expert * n_shared if 0
    router_aux_coef: float = 0.01
    impl: str = "ragged"           # 'ragged' (lax.ragged_dot) | 'dense' (one-hot)
    capacity_factor: float = 1.25  # only for the dense impl
    # the experts this layer holds: n_held from held_offset (0 = all); the
    # router still scores all n_experts (expert parallelism's share)
    n_held: int = 0
    held_offset: int = 0
    norm_topk_prob: bool = True    # renormalise the top-k weights to sum 1
    # DeepSeek-V2's sequence-wise balance loss in place of the Switch-style
    # batch loss
    seq_aux: bool = False
    router_f32: bool = False       # the router's logits in float32 (DeepSeek)
    # DeepSeek-V2's device-level budget ('ragged' only): per sequence the
    # held experts keep at most budget(t) of the pairs routed to them, those
    # of the highest routing weight, and compute budget(t) rows whatever
    # the routing; a training rule (a decoded token is a sequence of one);
    # 0: every pair kept, no budget
    device_capacity: float = 0.0

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts

    def budget(self, t: int) -> int:
        """The pairs a sequence of t tokens may keep on the held experts."""
        room = t * min(self.top_k, self.held)
        if not self.device_capacity:
            return room
        return min(room, math.ceil(self.device_capacity * t * self.top_k
                                   * self.held / self.n_experts))


@dataclass(frozen=True)
class MambaConfig:
    d_state: int = 128
    expand: int = 2
    head_dim: int = 64
    conv_width: int = 4
    chunk: int = 256               # SSD chunk length
    ngroups: int = 1


@dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536        # 0: a direct query projection (no LoRA)
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling as DeepSeek-V2 states it (``rope_scaling`` of its
    config.json, type "yarn")."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                 # dense | moe | ssm | hybrid | vlm | audio
    source: str                    # citation for the numbers
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # layer layout: n_layers == len(prefix) + n_periods * len(schedule)
    schedule: Tuple[LayerSpec, ...] = (LayerSpec(),)
    prefix: Tuple[LayerSpec, ...] = ()
    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    mla: Optional[MLAConfig] = None
    # misc architectural knobs
    rope_theta: float = 10_000.0
    rope_scaling: Optional[YarnScaling] = None   # read by MLA
    logit_softcap: float = 0.0
    attn_softcap: float = 0.0
    qk_norm: bool = False
    nonparametric_ln: bool = False # OLMo-style LN without learnable affine
    tie_embeddings: bool = False
    # encoder-decoder
    encdec: bool = False
    n_enc_layers: int = 0
    # modality frontend stub ('' | 'vision' | 'audio'); stubbed embeddings are
    # provided by input_specs() per the assignment carve-out.
    frontend: str = ""
    n_frontend_tokens: int = 0     # image/audio tokens included in the seq
    # long-context support
    long_500k_ok: bool = False
    long_ctx_window: int = 0       # >0: sliding-window variant used for long_500k
    long_500k_note: str = ""
    # dtypes
    dtype: str = "bfloat16"        # activation / compute dtype
    param_dtype: str = "float32"
    notes: str = ""

    @property
    def n_periods(self) -> int:
        body = self.n_layers - len(self.prefix)
        assert body % len(self.schedule) == 0, (
            f"{self.name}: {self.n_layers} layers, prefix {len(self.prefix)}, "
            f"period {len(self.schedule)} does not divide")
        return body // len(self.schedule)

    def replace(self, **kw) -> ModelConfig:
        return dataclasses.replace(self, **kw)

    def with_long_variant(self) -> ModelConfig:
        """Sliding-window variant used only for the long_500k shape."""
        if self.long_ctx_window <= 0:
            return self
        sched = tuple(
            dataclasses.replace(s, attn=ATTN_SLIDING, window=self.long_ctx_window)
            if s.kind == KIND_ATTN and s.attn == ATTN_FULL else s
            for s in self.schedule)
        pre = tuple(
            dataclasses.replace(s, attn=ATTN_SLIDING, window=self.long_ctx_window)
            if s.kind == KIND_ATTN and s.attn == ATTN_FULL else s
            for s in self.prefix)
        return self.replace(schedule=sched, prefix=pre)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",   524_288, 1,   "decode"),
}


# ---------------------------------------------------------------------------
# Federation (QuAFL) configuration — paper Alg. 1 knobs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FedConfig:
    n_clients: int = 16            # n in the paper
    s: int = 16                    # sampled clients per round
    local_steps: int = 4           # K
    lr: float = 0.1                # eta (client SGD step)
    # paper App. A: 'Unless otherwise noted, we employ the unweighted version'
    weighted: bool = False         # eta_i = H_min / H_i dampening
    bits: int = 8
    # per-direction codec specs (repro.compression.codecs registry names,
    # e.g. 'lattice_packed', 'scalar:bits=4', 'topk_ef:frac=0.01'); "" =
    # the algorithm's own default at `bits` — every registry algorithm
    # resolves its uplink/downlink compression from these unless given
    # explicit uplink=/downlink= kwargs
    codec_up: str = ""
    codec_down: str = ""
    # compression-pipeline kernel backend (repro.compression.pipeline):
    #  'jnp'              — pure-jnp composition (CPU CI default)
    #  'pallas_interpret' — Pallas kernels through the interpreter (CPU
    #                       validation of the exact TPU code path)
    #  'pallas'           — compiled Pallas kernels (real TPU)
    kernel_backend: str = "jnp"
    # client speed model (App. A timing experiments): step time ~ Exp(lam)
    slow_frac: float = 0.3
    lam_fast: float = 0.5
    lam_slow: float = 0.125
    swt: float = 10.0              # server waiting time between calls
    sit: float = 1.0               # server interaction time
    # client participation/availability spec (repro.fed.population
    # registry: 'uniform' | 'gamma_straggler[:strength=a]' |
    # 'cyclic:period=P,phase_groups=G'); "" = uniform — the paper's s-of-n
    # sampling without replacement, preserved draw-for-draw
    participation: str = ""
    # distribution of H_i^t used inside the SPMD train_step
    # 'binomial' -> H ~ Binomial(K, p_i); faithful "partial progress" draws
    h_dist: str = "binomial"
    seed: int = 0
    # aggregation transport on the mesh:
    #  'dequant_psum'  — faithful: decode locally then all-reduce fp32
    #  'code_allgather'— beyond-paper: all-gather packed codes, decode after
    #  'shard_local' / 'shard_local_codes' / 'shard_local_rs' — the whole
    #  exchange inside one shard_map (repro.core.exchange_local), client
    #  sum carried by the named repro.compression.transports strategy
    #  (fp32 psum / packed-code all-gather / fused reduce_scatter with the
    #  scatter-resident coded re-gather)
    transport: str = "dequant_psum"


@dataclass(frozen=True)
class MeshConfig:
    data: int = 16
    model: int = 16
    pods: int = 1

    @property
    def n_devices(self) -> int:
        return self.data * self.model * self.pods


@dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig = None
    fed: FedConfig = FedConfig()
    mesh: MeshConfig = MeshConfig()
    seq_len: int = 4096
    global_batch: int = 256
    steps: int = 100
    eval_every: int = 20
    remat: bool = True
    seq_shard_residual: bool = False  # Megatron-style sequence sharding of the residual stream
    log_every: int = 10
    checkpoint_dir: str = ""
    checkpoint_every: int = 0
