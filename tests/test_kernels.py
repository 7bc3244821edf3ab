"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.analysis.opbudget import (EXCHANGE_BLOCKS, EXCHANGE_GRID_STEPS,
                                     exchange_grid_counts)
from repro.kernels.exchange import (fused_decode, fused_encode, fused_rotate,
                                    pack_codes, quantize_codes, snap_codes)
from repro.kernels.flash_attention import flash_attention
from repro.compression.rotation import _signs, pad_len, rotate


@pytest.mark.parametrize("n,r,c,dtype", [
    (1, 128, 128, jnp.float32), (3, 128, 128, jnp.float32),
    (4, 64, 64, jnp.float32), (2, 128, 64, jnp.float32),
    (7, 16, 16, jnp.float32), (2, 128, 128, jnp.float32),
    (2, 128, 128, jnp.bfloat16)])
def test_fused_rotate_matches_hadamard_ref(n, r, c, dtype):
    """The round's rotation kernel with unit signs is the blocked
    H_r X H_c / sqrt(rc) of the oracle, at every block geometry; input
    blocks are cast to float32 inside the kernel."""
    x = jax.random.normal(jax.random.PRNGKey(0), (n, r, c)).astype(dtype)
    out = fused_rotate(x.reshape(1, -1), jnp.ones((n * r * c,), jnp.float32),
                       block=r * c, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out).reshape(n, r, c),
        np.asarray(ref.hadamard_ref(x.astype(jnp.float32))),
        atol=1e-1 if dtype == jnp.bfloat16 else 1e-4)


# ---------------------------------------------------------------------------
# fused exchange kernels (batched) vs per-message oracles
# d values include non-multiples of the 16384 rotation block (padding edges)
# ---------------------------------------------------------------------------

def _oracle_rows(d, s, key):
    """(s, d) messages + shared signs/noise/per-row gammas + oracle rotate."""
    d_pad = pad_len(d)
    krot = jax.random.fold_in(key, 0)
    signs = _signs(krot, d_pad)
    x = jax.random.normal(jax.random.fold_in(key, 1), (s, d)) * 2.0
    u = jax.random.uniform(jax.random.fold_in(key, 2), (s, d_pad))
    gammas = 0.01 * (1.0 + jnp.arange(s, dtype=jnp.float32))
    y_rows = jnp.stack([rotate(x[i], krot) for i in range(s)])
    return x, u, gammas, signs, krot, y_rows


@pytest.mark.parametrize("d,s,bits", [(1000, 3, 4), (5000, 4, 8),
                                      (20000, 2, 16), (16384, 5, 8),
                                      (1024, 1, 4), (8192, 1, 8),
                                      (4096, 1, 12), (65536, 1, 8)])
def test_fused_encode_matches_vmapped_oracle(d, s, bits):
    key = jax.random.PRNGKey(10)
    x, u, gammas, signs, krot, y_rows = _oracle_rows(d, s, key)
    d_pad = pad_len(d)
    x_pad = jnp.pad(x, ((0, 0), (0, d_pad - d)))
    y_rot, codes = fused_encode(x_pad, signs, u, gammas, bits=bits,
                                want_rotated=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y_rot), np.asarray(y_rows),
                               atol=1e-4)
    codes_ref = jnp.stack([
        ref.lattice_encode_ref(y_rows[i], u[i], gammas[i], bits)
        for i in range(s)])
    # the oracle's rotation sums in another order, so its coordinates may
    # differ from the kernel's in the last ulps. A code may then differ only
    # where the oracle's y/γ + u lies within a few ulps of an integer (a
    # floor boundary), by one step, and rarely.
    codes, codes_ref = np.asarray(codes), np.asarray(codes_ref)
    miss = codes != codes_ref
    t = (np.asarray(y_rows) / np.asarray(gammas)[:, None]
         + np.asarray(u)).astype(np.float32)
    scale = np.maximum(np.abs(np.asarray(y_rows) / np.asarray(gammas)[:, None]),
                       1.0).astype(np.float32)
    near = np.abs(t - np.round(t)) <= 8 * np.spacing(scale)
    assert np.all(near[miss]), "a code differs away from a floor boundary"
    step = (codes.astype(np.int64) - codes_ref) % (1 << bits)
    assert np.all(np.isin(step[miss], (1, (1 << bits) - 1)))
    assert miss.mean() <= 1e-3, miss.mean()


@pytest.mark.parametrize("d,s,bits", [(1000, 3, 4), (5000, 4, 8),
                                      (20000, 2, 16)])
def test_snap_codes_matches_vmapped_oracle(d, s, bits):
    key = jax.random.PRNGKey(11)
    x, u, gammas, signs, krot, y_rows = _oracle_rows(d, s, key)
    codes = jnp.stack([ref.lattice_encode_ref(y_rows[i], u[i], gammas[i],
                                              bits) for i in range(s)])
    w = y_rows[0:1] + 0.001   # shared rotated reference, broadcast over s
    out = snap_codes(codes, w, gammas, bits=bits, interpret=True)
    exp = jnp.stack([ref.lattice_decode_ref(codes[i], w[0], gammas[i], bits)
                     for i in range(s)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-6)


@pytest.mark.parametrize("d,s,bits", [(1000, 3, 4), (5000, 4, 8),
                                      (20000, 2, 16), (1024, 1, 4),
                                      (8192, 1, 8), (4096, 1, 12),
                                      (65536, 1, 8)])
def test_fused_decode_matches_composed_oracle(d, s, bits):
    """One broadcast message decoded against s references == per-row
    rotate-ref / snap / inverse-rotate composition."""
    key = jax.random.PRNGKey(12)
    x, u, gammas, signs, krot, y_rows = _oracle_rows(d, s, key)
    d_pad = pad_len(d)
    gamma = gammas[0:1]
    codes = ref.lattice_encode_ref(y_rows[0], u[0], gamma[0], bits)[None]
    refs = x[0][None] + 0.002 * jax.random.normal(
        jax.random.fold_in(key, 3), (s, d))
    refs_pad = jnp.pad(refs, ((0, 0), (0, d_pad - d)))
    out = fused_decode(codes, refs_pad, signs, gamma, bits=bits,
                       interpret=True)[:, :d]
    exp = jnp.stack([
        rotate(ref.lattice_decode_ref(codes[0], rotate(refs[i], krot),
                                      gamma[0], bits),
               krot, inverse=True)[:d]
        for i in range(s)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)


@pytest.mark.parametrize("s", [3, 1])
def test_fused_rotate_roundtrip_batched(s):
    d = 50_000
    key = jax.random.PRNGKey(13)
    signs = _signs(key, pad_len(d))
    x = jax.random.normal(jax.random.fold_in(key, 1), (s, d))
    x_pad = jnp.pad(x, ((0, 0), (0, pad_len(d) - d)))
    y = fused_rotate(x_pad, signs, interpret=True)
    np.testing.assert_allclose(
        np.asarray(jnp.stack([rotate(x[i], key) for i in range(s)])),
        np.asarray(y), atol=1e-4)
    back = fused_rotate(y, signs, inverse=True, interpret=True)[:, :d]
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=1e-4)


# (nb, m, g): nb Hadamard blocks of 128 x 128, m messages, the blocks a
# grid step is planned to take
GROUPED = [(12, 3, 4), (5, 1, 1), (1, 3, 1), (2, 1, 2)]
WIRES = [(1, False), (2, False), (1, True), (2, True)]   # (pack, levels row)


def _grouped_operands(nb, m, pack):
    b = 128 * 128
    key = jax.random.PRNGKey(nb * 10 + m)
    bits = 8 // pack
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, nb * b))
    u = jax.random.uniform(jax.random.fold_in(key, 2), (m, nb * b))
    codes = jax.random.randint(jax.random.fold_in(key, 3), (m, nb * b), 0,
                               1 << bits).astype(jnp.uint32)
    if pack > 1:
        codes = pack_codes(codes, bits=bits)
    gammas = 0.01 * (1.0 + jnp.arange(m, dtype=jnp.float32))
    levels = (2.0 ** (bits - jnp.arange(m) % 3)).astype(jnp.float32)
    return x, u, codes, gammas, levels, _signs(key, nb * b), bits


def _run_kernel(kernel, x, u, codes, gammas, levels, signs, bits, pack):
    """One call of ``kernel`` over whatever blocks its operands hold; the
    (m, *) operands broadcast where their leading dim is 1."""
    kw = dict(bits=bits, pack=pack, levels2=levels, interpret=True)
    if kernel == "rotate":
        return (fused_rotate(x, signs, interpret=True),
                fused_rotate(x, signs, inverse=True, interpret=True))
    if kernel == "encode":
        return fused_encode(x, signs, u, gammas, want_rotated=True, **kw)
    if kernel == "quantize":
        return quantize_codes(x, u, gammas, **kw)
    if kernel == "snap":     # one rotated reference for every message
        return snap_codes(codes, x[:1], gammas, **kw)
    # one message decoded against every reference
    return fused_decode(codes[:1], x, signs, gammas[:1], bits=bits,
                        pack=pack, interpret=True,
                        levels2=None if levels is None else levels[:1])


GROUPED_CASES = [
    pytest.param(kernel, nb, m, g, pack, levels,
                 id=f"{kernel}-nb{nb}-m{m}"
                 + (f"-pack{pack}" + ("-levels" if levels else "")
                    if kernel != "rotate" else ""))
    for kernel in ("rotate", "encode", "quantize", "snap", "decode")
    for nb, m, g in GROUPED
    for pack, levels in (WIRES if kernel != "rotate" else WIRES[:1])]


@pytest.mark.parametrize("kernel,nb,m,g,pack,levels", GROUPED_CASES)
def test_grouped_grid_matches_one_block_steps(kernel, nb, m, g, pack,
                                              levels):
    """A grid step over g Hadamard blocks gives, bit for bit, what a step
    of one block gives: the reference runs each block as its own call,
    whose grid is one block a step."""
    b = 128 * 128
    x, u, codes, gammas, lv, signs, bits = _grouped_operands(nb, m, pack)
    lv = lv if levels else None
    with exchange_grid_counts() as counts:
        got = _run_kernel(kernel, x, u, codes, gammas, lv, signs, bits, pack)
    calls = 2 if kernel == "rotate" else 1
    assert counts.get(EXCHANGE_BLOCKS) == calls * m * nb
    assert counts.get(EXCHANGE_GRID_STEPS) == calls * m * nb // g

    cb = b // pack
    per_block = [_run_kernel(kernel, x[:, j * b:(j + 1) * b],
                             u[:, j * b:(j + 1) * b],
                             codes[:, j * cb:(j + 1) * cb], gammas, lv,
                             signs[j * b:(j + 1) * b], bits, pack)
                 for j in range(nb)]
    want = jax.tree_util.tree_map(lambda *bl: jnp.concatenate(bl, axis=1),
                                  *per_block)
    for a, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == w.dtype and a.shape == w.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


@pytest.mark.parametrize(
    "b,t,h,kv,dh,window,cap",
    [(2, 256, 4, 2, 64, 0, 0.0),      # GQA causal
     (1, 512, 8, 8, 32, 0, 0.0),      # MHA long
     (1, 256, 8, 2, 64, 128, 0.0),    # sliding window
     (2, 128, 4, 1, 64, 0, 50.0),     # MQA + softcap (gemma)
     (1, 256, 4, 2, 128, 64, 30.0)])  # window + softcap
def test_flash_attention_sweep(b, t, h, kv, dh, window, cap):
    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, t, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kv, dh), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, softcap=cap,
                          block_q=64, block_k=64, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=window,
                                  softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 64)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 64)).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=64, block_k=64,
                          interpret=True)
    exp = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=3e-2)


def test_flash_attention_matches_model_attention():
    """The kernel is a drop-in for the model's chunked sdpa path."""
    from repro.configs.base import LayerSpec
    from repro.configs import get_reduced
    from repro.models.attention import attention_prefill
    cfg = get_reduced("llama3.2-1b")
    spec = LayerSpec()
    key = jax.random.PRNGKey(6)
    ks = jax.random.split(key, 3)
    b, t = 1, 256
    q = jax.random.normal(ks[0], (b, t, cfg.n_heads, cfg.head_dim))
    k = jax.random.normal(ks[1], (b, t, cfg.n_kv_heads, cfg.head_dim))
    v = jax.random.normal(ks[2], (b, t, cfg.n_kv_heads, cfg.head_dim))
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, block_q=64, block_k=64,
                          interpret=True)),
        np.asarray(attention_prefill(cfg, spec, q, k, v)), atol=2e-5)
