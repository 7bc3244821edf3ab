"""Lattice and scalar codec + rotation properties (paper Lemma 3.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.compression import (LatticeCodec, ScalarCodec, make_codec,
                               pad_len, rotate, wrap_gamma)


# --------------------------------------------------------------------------
# rotation: orthonormal, involutive (up to signs), deterministic in key
# --------------------------------------------------------------------------

@settings(deadline=None, max_examples=20)
@given(d=st.integers(8, 5000), seed=st.integers(0, 2**31 - 1))
def test_rotation_norm_preserving(d, seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    y = rotate(x, key)
    assert y.shape[0] == pad_len(d)
    np.testing.assert_allclose(float(jnp.linalg.norm(y)),
                               float(jnp.linalg.norm(x)), rtol=1e-4)


@settings(deadline=None, max_examples=20)
@given(d=st.integers(8, 5000), seed=st.integers(0, 2**31 - 1))
def test_rotation_inverse(d, seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    xr = rotate(rotate(x, key), key, inverse=True)[:d]
    np.testing.assert_allclose(np.asarray(xr), np.asarray(x), atol=1e-4)


# --------------------------------------------------------------------------
# lattice quantizer: Lemma 3.1 properties
# --------------------------------------------------------------------------

@settings(deadline=None, max_examples=15)
@given(bits=st.integers(6, 12), dist=st.floats(1e-3, 10.0),
       seed=st.integers(0, 1000))
def test_lattice_error_proportional_to_distance(bits, dist, seed):
    """Property 2: ‖Q(x) − x‖ ≤ C(b)·‖x − y‖, independent of ‖x‖."""
    d = 4097
    q = LatticeCodec(bits=bits)
    key = jax.random.PRNGKey(seed)
    ref = jax.random.normal(key, (d,)) * 100.0  # large-norm reference
    delta = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    x = ref + delta * (dist / float(jnp.linalg.norm(delta)))
    msg = q.encode(key, x, jnp.float32(dist))
    xh = q.decode(key, msg, ref)
    err = float(jnp.linalg.norm(xh - x))
    # γ·sqrt(d_pad) bound (γ from the message: includes the precision floor)
    bound = float(msg.gamma) * np.sqrt(pad_len(d))
    assert err <= bound * 1.01, (err, bound)
    # error scales with the DISTANCE (plus the fp32 floor of the model norm),
    # not with the 100x larger reference norm itself
    norm_floor = 100.0 * np.sqrt(d) * 2.0 ** -18 * np.sqrt(pad_len(d))
    assert err <= 2.0 * dist + norm_floor


def test_lattice_unbiased():
    """Property 1: E[Dec(y, Enc(x))] = x (stochastic rounding)."""
    d, N = 2000, 300
    q = LatticeCodec(bits=6)
    key = jax.random.PRNGKey(0)
    ref = jax.random.normal(key, (d,)) * 5
    x = ref + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (d,))
    dist = jnp.linalg.norm(x - ref)

    def one(i):
        k = jax.random.fold_in(key, 100 + i)
        return q.decode(k, q.encode(k, x, dist), ref)

    mean = jax.lax.map(one, jnp.arange(N)).mean(0)
    bias = float(jnp.linalg.norm(mean - x))
    per_coord = float(wrap_gamma(dist, d, bits=q.bits, block=q.block,
                                 safety=q.safety))
    # bias ≈ γ·sqrt(d/12N) for unbiased SR; allow 5 sigma
    assert bias <= 5 * per_coord * np.sqrt(d / (12 * N)), bias


def test_lattice_bits_accounting():
    q = LatticeCodec(bits=8)
    assert q.message_bits(16384) == 16384 * 8 + 32
    assert q.message_bits(16385) == 2 * 16384 * 8 + 32  # padded


@settings(deadline=None, max_examples=10)
@given(bits=st.integers(4, 10), seed=st.integers(0, 100))
def test_qsgd_unbiased_small(bits, seed):
    d, N = 256, 400
    q = ScalarCodec(bits=bits)
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (d,))

    def one(i):
        k = jax.random.fold_in(key, i)
        return q.decode(k, q.encode(k, x))

    mean = jax.lax.map(one, jnp.arange(N)).mean(0)
    err = float(jnp.linalg.norm(mean - x)) / float(jnp.linalg.norm(x))
    assert err < 0.2, err


def test_make_codec_registry():
    for name in ("lattice", "lattice_packed", "topk_ef", "scalar",
                 "identity"):
        assert make_codec(name, bits=8).name == name
    with pytest.raises(ValueError):
        make_codec("bogus", bits=8)


def test_wrap_failure_mode():
    """When the decoder's reference is FAR beyond the wrap window the
    positional decode is wrong — the regime Lemma 3.4's potential bound
    exists to prevent."""
    d = 1024
    q = LatticeCodec(bits=4, safety=1.0)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (d,))
    msg = q.encode(key, x, jnp.float32(0.01))  # hint far too small
    ref = x + jax.random.normal(jax.random.fold_in(key, 1), (d,)) * 10.0
    xh = q.decode(key, msg, ref)
    assert float(jnp.linalg.norm(xh - x)) > 1.0
