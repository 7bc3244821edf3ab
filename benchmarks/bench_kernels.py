"""Kernel microbenchmarks: Pallas (interpret) vs pure-jnp reference.

On CPU the interpret-mode timing is NOT a TPU projection — the derived
column therefore reports the analytic FLOP/byte counts used by the roofline
model, plus wall-time of the jnp reference path for regression tracking."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression.rotation import _signs, pad_len, rotate
from repro.kernels.exchange import fused_rotate
from repro.kernels.ref import flash_attention_ref, hadamard_ref
from benchmarks.common import emit


def _time(f, *args, n=5):
    # exactly ONE warm-up call (the old version evaluated f twice while
    # dispatching on the result type, skewing cache state for tiny kernels)
    jax.block_until_ready(f(*args))
    t0 = time.time()
    for _ in range(n):
        jax.block_until_ready(f(*args))
    return (time.time() - t0) / n * 1e6


def main():
    key = jax.random.PRNGKey(0)
    # rotation over a 10M-param model vector
    d = 10_000_000
    x = jax.random.normal(key, (d,))
    rot = jax.jit(lambda v: rotate(v, key))
    us = _time(rot, x)
    flops = 2 * d * (128 + 128)  # two 128-matmuls per element block
    emit("rotate_10M", us, f"flops={flops:.3g};bytes={d*4*2:.3g}")

    # jnp reference vs Pallas-interpret on the same 1M vector (interpret
    # executes the grid serially on CPU — a validation datapoint, not a
    # TPU projection; see module docstring)
    d1 = 1 << 20
    x1 = jax.random.normal(key, (d1,))
    us = _time(jax.jit(lambda v: rotate(v, key)), x1, n=3)
    emit("rotate_1M_jnp", us, f"flops={2*d1*(128+128):.3g};bytes={d1*4*2:.3g}")
    signs = _signs(key, pad_len(d1))
    x1p = x1[None]
    us = _time(lambda v: fused_rotate(v, signs, interpret=True), x1p, n=1)
    emit("rotate_1M_pallas_interpret", us,
         f"flops={2*d1*(128+128):.3g};bytes={d1*4*2:.3g}")

    # flash attention tile at the prefill_32k working point (scaled down)
    b, t, h, kv, dh = 1, 2048, 8, 2, 128
    q = jax.random.normal(key, (b, t, h, dh), jnp.bfloat16)
    k = jax.random.normal(key, (b, t, kv, dh), jnp.bfloat16)
    v = jax.random.normal(key, (b, t, kv, dh), jnp.bfloat16)
    att = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v))
    us = _time(att, q, k, v)
    emit("attention_ref_2k", us,
         f"flops={4*b*h*t*t*dh:.3g};bytes={(q.size+k.size+v.size)*2:.3g}")


if __name__ == "__main__":
    main()
