"""Per-architecture smoke tests (assignment requirement): a REDUCED variant
of each family runs one forward/train step on CPU — shapes + no NaNs — plus
decode-vs-full-forward equivalence, the strongest cache-correctness check."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, get_reduced, list_archs
from repro.models import decode_step, forward, init_cache, init_lm, lm_loss

ARCHS = [a for a in list_archs() if a != "paper-mlp"]


def _batch(cfg, key, b=2, t=32, enc_len=16):
    batch = {"tokens": jax.random.randint(key, (b, t), 0, cfg.vocab_size)}
    if cfg.encdec:
        batch["frontend"] = jax.random.normal(key, (b, enc_len, cfg.d_model))
    elif cfg.frontend:
        batch["frontend"] = jax.random.normal(
            key, (b, cfg.n_frontend_tokens, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_constraints(arch):
    cfg = get_reduced(arch)
    assert cfg.n_layers <= 2
    assert cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    expect = {
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "gemma2-2b": (26, 2304, 8, 4, 9216, 256000),
        "deepseek-v2-236b": (60, 5120, 128, 128, 12288, 102400),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
        "mamba2-370m": (48, 1024, 32, 0, 0, 50280),
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "olmo-1b": (16, 2048, 16, 16, 8192, 50304),
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab_size)
    assert got == expect, (got, expect)
    # schedule consistency
    assert cfg.n_periods * len(cfg.schedule) + len(cfg.prefix) == cfg.n_layers


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    """One forward + one SGD train step on the reduced family member."""
    cfg = get_reduced(arch)
    key = jax.random.PRNGKey(0)
    params, _ = init_lm(cfg, key)
    batch = _batch(cfg, key)
    logits, _, aux = forward(cfg, params, batch)
    t_text = batch["tokens"].shape[1]
    assert logits.shape == (2, t_text, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())

    (loss, _), grads = jax.value_and_grad(
        lambda p: lm_loss(cfg, p, batch), has_aux=True)(params)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in grads.values())
    assert np.isfinite(gn) and gn > 0
    new = {k: params[k] - 0.01 * grads[k] for k in params}
    loss2, _ = lm_loss(cfg, new, batch)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = get_reduced(arch)
    key = jax.random.PRNGKey(1)
    params, _ = init_lm(cfg, key)
    b, t, t0 = 2, 24, 16
    batch = _batch(cfg, key, b=b, t=t)
    logits_full, _, _ = forward(cfg, params, batch)
    enc_len = 16 if cfg.encdec else 0
    cache = init_cache(cfg, b, max_seq=t + 16, enc_len=enc_len)
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :t0]
    logits_pre, cache, _ = forward(cfg, params, pre, cache=cache, write_pos=0)
    outs = [logits_pre[:, -1]]
    off = cfg.n_frontend_tokens if (cfg.frontend and not cfg.encdec) else 0
    for pos in range(t0, t):
        lg, cache = decode_step(cfg, params, batch["tokens"][:, pos:pos + 1],
                                jnp.int32(pos + off), cache)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec),
                               np.asarray(logits_full[:, t0 - 1:t]),
                               atol=2e-4, rtol=2e-3)


def test_long_variant_schedule():
    cfg = get_config("olmo-1b").with_long_variant()
    assert all(s.attn == "sliding" and s.window == 8192
               for s in cfg.schedule)
    # archs without a window variant are unchanged
    cfg2 = get_config("llava-next-34b").with_long_variant()
    assert all(s.attn == "full" for s in cfg2.schedule)


def test_sliding_ring_cache_decode():
    """Decode beyond the window with a ring cache == full forward."""
    cfg = get_reduced("gemma2-2b")  # has a sliding layer (window 64 reduced)
    key = jax.random.PRNGKey(2)
    params, _ = init_lm(cfg, key)
    b, t = 1, 96  # > window 64
    toks = jax.random.randint(key, (b, t), 0, cfg.vocab_size)
    logits_full, _, _ = forward(cfg, params, {"tokens": toks})
    cache = init_cache(cfg, b, max_seq=t)
    t0 = 80
    _, cache, _ = forward(cfg, params, {"tokens": toks[:, :t0]}, cache=cache)
    outs = []
    for pos in range(t0, t):
        lg, cache = decode_step(cfg, params, toks[:, pos:pos + 1],
                                jnp.int32(pos), cache)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec),
                               np.asarray(logits_full[:, t0:t]),
                               atol=2e-4, rtol=2e-3)


def test_moe_dense_vs_ragged_impl():
    """The two MoE implementations agree when capacity is ample."""
    import dataclasses
    cfg = get_reduced("llama4-scout-17b-a16e")
    key = jax.random.PRNGKey(3)
    params, _ = init_lm(cfg, key)
    batch = _batch(cfg, key)
    lr, _, _ = forward(cfg, params, batch)
    cfg_d = cfg.replace(moe=dataclasses.replace(cfg.moe, impl="dense",
                                                capacity_factor=8.0))
    ld, _, _ = forward(cfg_d, params, batch)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(ld), atol=2e-4,
                               rtol=2e-3)
