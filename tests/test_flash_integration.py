"""The Pallas flash-attention kernel as a drop-in for the model's prefill
path: full model forward with the kernel (interpreted) must match the jnp
path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import forward, init_lm
from repro.models import attention as A


@pytest.mark.parametrize("arch", ["llama3.2-1b", "gemma2-2b", "olmo-1b"])
def test_forward_with_flash_kernel_matches(arch):
    cfg = get_reduced(arch)
    if arch == "gemma2-2b":
        # reduced gemma2 window is 64 < t: exercises the sliding flash path
        pass
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                              cfg.vocab_size)
    ref, _, _ = forward(cfg, params, {"tokens": toks})
    A.FLASH_KERNEL = "pallas_interpret"
    try:
        out, _, _ = forward(cfg, params, {"tokens": toks})
    finally:
        A.FLASH_KERNEL = None
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3,
                               rtol=2e-2)


def test_flash_fallback_on_chunked():
    """llama4 chunked-local layers must silently fall back to the jnp path."""
    cfg = get_reduced("llama4-scout-17b-a16e")
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                              cfg.vocab_size)
    ref, _, _ = forward(cfg, params, {"tokens": toks})
    A.FLASH_KERNEL = "pallas_interpret"
    try:
        out, _, _ = forward(cfg, params, {"tokens": toks})
    finally:
        A.FLASH_KERNEL = None
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3,
                               rtol=2e-2)
