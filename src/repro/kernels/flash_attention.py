"""Pallas TPU kernel: causal GQA flash attention (online softmax).

The prefill hot-spot for the 32k shapes. Tiling: grid (b·h, n_q_blocks,
n_kv_blocks) with the kv dimension innermost ('arbitrary' semantics); the
running max/denominator/accumulator live in VMEM scratch and persist across
kv steps. Per-step VMEM: bq·dh (q) + bk·dh (k,v) + bq·bk (scores) floats —
(128, 128, 512)-tiles ≈ 0.6 MiB, MXU-aligned.

Supports sliding-window and logit-softcap variants (gemma2/gemma3/llama4
schedules). GQA is handled in the k/v index_map: q-head ih reads kv-head
ih // group.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, softcap: float, causal: bool, window: int,
                  bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)          # (bq, dh)
    k = k_ref[0].astype(jnp.float32)          # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jax.lax.dot(p, v, preferred_element_type=jnp.float32))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@partial(jax.jit,
         static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                          "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool):
    """q: (b, tq, h, dh); k, v: (b, tk, kv, dh) with h % kv == 0."""
    b, tq, h, dh = q.shape
    tk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    bq = min(block_q, tq)
    bk = min(block_k, tk)
    assert tq % bq == 0 and tk % bk == 0, (tq, bq, tk, bk)
    nq, nk = tq // bq, tk // bk
    scale = 1.0 / np.sqrt(dh)

    qh = jnp.moveaxis(q, 2, 1).reshape(b * h, tq, dh)
    kh = jnp.moveaxis(k, 2, 1).reshape(b * kvh, tk, dh)
    vh = jnp.moveaxis(v, 2, 1).reshape(b * kvh, tk, dh)

    def kv_index(ih, qi, ki):
        return (ih // h) * kvh + (ih % h) // g, ki, 0

    out = pl.pallas_call(
        partial(_flash_kernel, scale=scale, softcap=softcap, causal=causal,
                window=window, bq=bq, bk=bk, nk=nk),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda ih, qi, ki: (ih, qi, 0)),
            pl.BlockSpec((1, bk, dh), kv_index),
            pl.BlockSpec((1, bk, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda ih, qi, ki: (ih, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return jnp.moveaxis(out.reshape(b, h, tq, dh), 1, 2)
