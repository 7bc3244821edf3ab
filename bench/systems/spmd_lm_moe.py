"""A DeepSeek-V2 mixture-of-experts language model trained through the
``spmd`` registry algorithm, one chip's share of an expert-parallel silo:
the cell of ``spmd_lm`` with the program's ModelConfig built from a
DeepSeek-V2 ``config.json`` (MLA, YaRN, a dense prefix, an MoE body whose
layers hold ``n_routed_experts_held`` of the routed experts).

Set-up also keeps the MoE counters of the check rounds, the pairs routed
to the held experts, those kept under the device budget and the rows the
grouped matmuls compute, which ``bench/metrics/moe_padded_rows_share.py``
reads; it prints them on stderr. The reference is
``spmd_lm_reference``'s, with the configuration's own model.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as traffic_mod
from bench.systems import spmd_lm

# what the program runs of DeepSeek-V2's config.json, key by key
_FIXED = {"model_type": "deepseek_v2", "hidden_act": "silu",
          "attention_bias": False, "q_lora_rank": None, "moe_layer_freq": 1,
          "n_group": 1, "topk_group": 1, "topk_method": "greedy",
          "scoring_func": "softmax", "routed_scaling_factor": 1,
          "tie_word_embeddings": False, "rms_norm_eps": 1e-6}


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file. Refuses, at
    once, what the program cannot run as the file states it, a program
    without the held-experts layer or YaRN among it."""
    wrong = {k: cfg.get(k) for k, v in _FIXED.items() if cfg.get(k) != v}
    if (cfg["rope_scaling"].get("type") != "yarn"
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]):
        wrong["rope_scaling/num_key_value_heads"] = "not yarn / grouped"
    if wrong:
        raise ValueError(f"the program runs DeepSeek-V2 with {_FIXED}, "
                         f"yarn rope and one key-value head per head; the "
                         f"configuration states {wrong}")
    try:
        from repro.configs.base import (ATTN_MLA, LayerSpec, MLAConfig,
                                        ModelConfig, MoEConfig, YarnScaling)
        moe = MoEConfig(
            n_experts=cfg["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_ff_expert=cfg["moe_intermediate_size"],
            n_shared=cfg["n_shared_experts"],
            d_ff_shared=cfg["moe_intermediate_size"]
            * cfg["n_shared_experts"],
            router_aux_coef=cfg["aux_loss_alpha"],
            n_held=cfg["n_routed_experts_held"],
            held_offset=cfg["n_routed_experts_offset"],
            norm_topk_prob=cfg["norm_topk_prob"], seq_aux=cfg["seq_aux"],
            router_f32=True,
            device_capacity=cfg["device_capacity_factor"])
    except (ImportError, TypeError) as e:
        raise RuntimeError(f"this program has no held-experts MoE layer or no "
                           f"YaRN rope: {e}") from None
    rs = cfg["rope_scaling"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dense = cfg["first_k_dense_replace"]
    return ModelConfig(
        name=cfg["name"], arch_type="moe", source=cfg["source"],
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=h, head_dim=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        prefix=(LayerSpec(attn=ATTN_MLA, mlp="dense"),) * dense,
        schedule=(LayerSpec(attn=ATTN_MLA, mlp="moe"),),
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_dim=cfg["qk_nope_head_dim"],
                      qk_rope_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=moe, rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        tie_embeddings=False, dtype=cfg["compute_dtype"],
        param_dtype=cfg["param_dtype"])


class Cell(spmd_lm.Cell):
    COUNTERS = ("moe_routed_rows", "moe_kept_rows", "moe_buffer_rows")

    def __init__(self, cfg: dict, traffic: dict, model, seed_key):
        self.mcfg = model_config(cfg)
        super().__init__(cfg, traffic, model, seed_key)

    def setup(self):
        from repro.fed import make_algorithm
        from repro.utils.compat import make_mesh
        cfg, tr = self.cfg, self.traffic
        fed = spmd_lm.fed_config(cfg, tr)
        params = jax.jit(lambda k: self.model.weights(cfg, k))(self.k_weights)
        self.data = jax.jit(lambda k: traffic_mod.make(
            k, tr, n_clients=fed.n_clients, vocab=cfg["vocab_size"]))(
            self.k_data)
        template = jax.eval_shape(lambda: params)
        self.alg = make_algorithm(
            "spmd", fed, loss_fn=None, template=template, batch_fn=None,
            cfg=self.mcfg, mesh=make_mesh(tuple(cfg["fed"]["mesh"]),
                                          ("data", "model")),
            batch=self.batch, seq=self.seq, remat=cfg["fed"]["remat"])
        self.state = self.alg.init(params)
        del params
        self.key = self.k_run
        change = jax.jit(self._change_norms)
        q, rows, update = [], [], None
        for r in range(tr["check_rounds"]):
            self.prepare()
            m = self.step()
            q.append(m["quant_err_sq"])
            rows.append([jnp.sum(m[k]) for k in self.COUNTERS])
            if r == 0:
                update = change(self.state.train, self.k_weights)
        last = change(self.state.train, self.k_weights)
        self.check = jax.device_get({
            "quant_err": [jnp.sqrt(x) for x in q],
            "update": update["server"], "change": last})
        self.moe_rows = dict(zip(
            ("routed", "kept", "buffer"),
            np.sum(jax.device_get(rows), axis=0).tolist()))
        print(f"[moe] {self.moe_rows}", file=sys.stderr, flush=True)
