"""Structural invariants of the model substrate (hypothesis-driven)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_reduced
from repro.configs.base import LayerSpec, MambaConfig
from repro.models import forward, init_lm
from repro.models.attention import attention_prefill
from repro.models.mamba import _ssd_chunked


@settings(deadline=None, max_examples=8)
@given(chunk=st.sampled_from([8, 16, 32, 64]), seed=st.integers(0, 50))
def test_ssd_chunk_size_invariance(chunk, seed):
    """The SSD dual form must be exact for ANY chunk length (the chunking is
    an implementation detail, not an approximation)."""
    key = jax.random.PRNGKey(seed)
    b, t, h, p, n = 1, 64, 2, 4, 8
    ks = jax.random.split(key, 4)
    xh = jax.random.normal(ks[0], (b, t, h, p))
    # small dt keeps the fp32 decay-product reassociation error well below
    # the tolerance (the identity is exact in real arithmetic; different
    # chunkings reassociate exp-cumsum products differently)
    dt = 0.3 * jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, t, 1, n)) * 0.3
    C = jax.random.normal(jax.random.fold_in(key, 9), (b, t, 1, n)) * 0.3
    y_ref, s_ref = _ssd_chunked(xh, dt, A, B, C, chunk=t)   # single chunk
    y, s = _ssd_chunked(xh, dt, A, B, C, chunk=chunk)
    scale = float(jnp.max(jnp.abs(y_ref))) + 1.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-3 * scale, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=2e-3, rtol=5e-3)


@settings(deadline=None, max_examples=6)
@given(t=st.sampled_from([256, 512]), window=st.sampled_from([0, 128]),
       seed=st.integers(0, 20))
def test_attention_query_chunk_invariance(t, window, seed):
    """The query-chunked scan path must equal the one-shot sdpa path."""
    cfg = get_reduced("llama3.2-1b")
    spec = (LayerSpec(attn="sliding", window=window) if window
            else LayerSpec())
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, t, 4, 16))
    k = jax.random.normal(ks[1], (1, t, 2, 16))
    v = jax.random.normal(ks[2], (1, t, 2, 16))
    chunked = attention_prefill(cfg, spec, q, k, v)  # t triggers the scan

    # one-shot reference via masked sdpa
    from repro.models.attention import sdpa
    pos = jnp.arange(t)
    mask = pos[:, None] >= pos[None, :]
    if window:
        mask &= pos[None, :] > pos[:, None] - window
    ref = sdpa(q, k, v, mask, 1.0 / np.sqrt(16), 0.0)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(ref),
                               atol=2e-5)


def test_forward_deterministic():
    cfg = get_reduced("gemma3-12b")
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    a, _, _ = forward(cfg, params, {"tokens": toks})
    b, _, _ = forward(cfg, params, {"tokens": toks})
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_forward_batch_independence():
    """Per-sequence outputs must not depend on batch companions."""
    cfg = get_reduced("olmo-1b")
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 24), 0,
                              cfg.vocab_size)
    full, _, _ = forward(cfg, params, {"tokens": toks})
    solo, _, _ = forward(cfg, params, {"tokens": toks[1:2]})
    np.testing.assert_allclose(np.asarray(full[1]), np.asarray(solo[0]),
                               atol=2e-4, rtol=2e-3)


def test_mamba_reduced_chunk_matches_decode_state():
    """Prefill final SSM state == state after token-by-token decode."""
    from repro.models import decode_step, init_cache
    cfg = get_reduced("mamba2-370m")
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                              cfg.vocab_size)
    cache_a = init_cache(cfg, 1, max_seq=32)
    _, cache_a, _ = forward(cfg, params, {"tokens": toks}, cache=cache_a)
    cache_b = init_cache(cfg, 1, max_seq=32)
    _, cache_b, _ = forward(cfg, params, {"tokens": toks[:, :1]},
                            cache=cache_b)
    for pos in range(1, 16):
        _, cache_b = decode_step(cfg, params, toks[:, pos:pos + 1],
                                 jnp.int32(pos), cache_b)
    for k in cache_a:
        if k.endswith("ssm"):
            np.testing.assert_allclose(np.asarray(cache_a[k]),
                                       np.asarray(cache_b[k]), atol=1e-3,
                                       rtol=1e-2)


def test_vocab_logits_shape_all_archs_tied_and_untied():
    for arch in ("gemma2-2b", "deepseek-v2-236b"):
        cfg = get_reduced(arch)
        params, _ = init_lm(cfg, jax.random.PRNGKey(0))
        toks = jnp.zeros((1, 8), jnp.int32)
        lg, _, _ = forward(cfg, params, {"tokens": toks})
        assert lg.shape == (1, 8, cfg.vocab_size)
