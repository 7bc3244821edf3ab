"""The exchange's noise draws keep jax.random's stream bit for bit.

``_signs`` and ``dither`` draw at a lane-dense shape and reshape to the
caller's. The benchmark's reference replays each draw with a plain
``jax.random`` call at the caller's shape, so every draw must equal that
call exactly: at any shape, for sizes that are and are not a multiple of
128, and under ``vmap``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression.rotation import _signs, dither

N = 40 * 128      # takes the dense draw
ODD = 1000        # not a multiple of 128: drawn at the caller's shape


def _draws(kind, shape):
    """(the program's draw, the plain jax.random draw) of one key."""
    if kind == "signs":
        return (lambda k: _signs(k, shape[0]),
                lambda k: jax.random.rademacher(k, shape, dtype=jnp.float32))
    return (lambda k: dither(k, shape),
            lambda k: jax.random.uniform(k, shape, jnp.float32))


# (kind, shape, keys): keys 0 draws from one key, keys m > 0 vmaps over m
CASES = ([(kind, shape, keys) for kind in ("signs", "dither")
          for shape, keys in [((N,), 0), ((N,), 1), ((N,), 3),
                              ((ODD,), 0), ((ODD,), 3)]]
         + [("dither", (1, N), 0), ("dither", (3, N), 0),
            ("dither", (3, ODD), 0), ("dither", (1, N), 3)])
CASE_IDS = [f"{kind}-{'x'.join(map(str, shape))}-" +
            (f"vmap{keys}" if keys else "one")
            for kind, shape, keys in CASES]


@pytest.mark.parametrize("kind,shape,keys", CASES, ids=CASE_IDS)
def test_noise_draw_is_the_plain_draw(kind, shape, keys):
    program, plain = _draws(kind, shape)
    key = jax.random.PRNGKey(20261018)
    if keys:
        key = jax.random.split(key, keys)
        program, plain = jax.vmap(program), jax.vmap(plain)
    got = jax.jit(program)(key)
    want = plain(key)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
