"""deepseek-v2-lite [moe] — MLA without q-LoRA (kv_lora=512), YaRN rope,
2 shared + 64 routed experts, top-6, un-renormalised weights.

27L d_model=2048 16H d_ff(expert)=1408 vocab=102400. The first layer is a
dense MLP (d_ff=10944), the other 26 are MoE; the balance loss is
DeepSeek-V2's sequence-wise one and the router works in float32. A
deployment may add DeepSeek-V2's device-level budget for training
(``MoEConfig.device_capacity``), as the benchmark's does. [arXiv:2405.04434;
huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]
"""
from repro.configs.base import (ATTN_MLA, LayerSpec, MLAConfig, ModelConfig,
                                MoEConfig, YarnScaling)

_MLA_DENSE = LayerSpec(attn=ATTN_MLA, mlp="dense")
_MLA_MOE = LayerSpec(attn=ATTN_MLA, mlp="moe")
_YARN = YarnScaling(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        arch_type="moe",
        source="arXiv:2405.04434",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
        d_ff=10_944, vocab_size=102_400,
        prefix=(_MLA_DENSE,),
        schedule=(_MLA_MOE,),
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512, qk_nope_dim=128,
                      qk_rope_dim=64, v_head_dim=128),
        # aux_loss_alpha 0.001 as DeepSeek-V2's released configs give it
        moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                      d_ff_shared=2816, router_aux_coef=0.001,
                      norm_topk_prob=False, seq_aux=True, router_f32=True),
        rope_theta=10_000.0,
        rope_scaling=_YARN,
    )


def reduced() -> ModelConfig:
    """Two layers, 4 experts of which this layer holds 2 (from expert 2)."""
    return config().replace(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=256, vocab_size=512,
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=32, qk_nope_dim=16,
                      qk_rope_dim=16, v_head_dim=16),
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=64, n_shared=2,
                      d_ff_shared=128, router_aux_coef=0.001,
                      norm_topk_prob=False, seq_aux=True, router_f32=True,
                      n_held=2, held_offset=2),
        param_dtype="float32", dtype="float32",
    )
