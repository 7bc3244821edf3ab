"""The DeepSeek-V2-Lite configuration against the program: its reference's
parameter shapes are the program's, and its FLOPs count the program's
matrix weights, the held routed experts at the share of a token they see.
Run as ``pytest bench/tests``.
"""
from __future__ import annotations

import json

from bench import harness

NAME = "deepseek-v2-lite-ep8"


def test_dsv2lite_flops_count_the_programs_matmul_weights():
    from repro.models.model import abstract_lm
    from bench.systems.spmd_lm_moe import model_config
    cfg = json.loads((harness.BENCH / "configs" / f"{NAME}.json").read_text())
    model = harness.load_module(harness.BENCH / "configs" / f"{NAME}.py",
                                "dsv2lite_ref")
    spec, _ = abstract_lm(model_config(cfg))
    assert {k: tuple(v.shape) for k, v in spec.items()} == model.shapes(cfg)
    assert sum(v.size for v in spec.values()) == 535_060_992
    # every matrix but the input lookup; the 8 held experts of each MoE
    # layer at 6 of 64 experts a token, 0.75 of the 8
    routed = sum(v.size for k, v in spec.items()
                 if k.startswith("body/0/moe/w_"))
    n = sum(v.size for k, v in spec.items()
            if len(v.shape) >= 2 and k != "embed/tok") - routed + routed * 6 // 64
    assert model.matmul_params(cfg) == n == 257_968_128
    # MLA: 16 heads of q.k over 128 + 64 and of the weighted sum over 128
    assert model.flops_per_token(cfg, 2048) == 6 * n + 6 * 5 * 2048 * 16 * 320
