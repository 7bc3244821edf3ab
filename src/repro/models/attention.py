"""GQA attention: full / sliding-window / chunked-local, prefill + decode.

Prefill is computed with a query-chunked ``lax.scan`` (flash-style tiling in
pure JAX) so the 32k shapes never materialize a full (t, t) score matrix and
the HLO stays compact. The Pallas flash-attention kernel in repro.kernels is
a drop-in replacement for the inner tile (TPU target; validated in interpret
mode).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN_CHUNKED, ATTN_SLIDING
from repro.models.layers import apply_rope, rms_norm, softcap

NEG_INF = -1e30

# §Perf switch: compute the QK contraction with bf16 partial sums. When the
# model axis over-splits head_dim (e.g. gemma2: 8 heads on a 16-way axis)
# GSPMD all-reduces score-matrix partials; emitting them in bf16 halves those
# bytes. Softmax still runs in fp32 after the (masked) upcast.
BF16_SCORE_PARTIALS = False

# Prefill through the Pallas flash-attention kernel (full/sliding causal
# layers; chunked-local and non-tile-aligned shapes fall back to the jnp
# path): None keeps the jnp path, "pallas" compiles the kernel for the TPU,
# "pallas_interpret" runs it through the Pallas interpreter (tests).
FLASH_KERNEL = None


def _score_dtype(q):
    return q.dtype if BF16_SCORE_PARTIALS else jnp.float32


def init_attention(ctx, cfg):
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ctx.param("wq", (d, h * dh), ("embed", "q_flat"))
    ctx.param("wk", (d, kv * dh), ("embed", "kv_flat"))
    ctx.param("wv", (d, kv * dh), ("embed", "kv_flat"))
    ctx.param("wo", (h * dh, d), ("q_flat", "embed"))
    if cfg.qk_norm:
        ctx.param("q_norm/scale", (dh,), (None,), init="zeros")
        ctx.param("k_norm/scale", (dh,), (None,), init="zeros")


def _qkv(cfg, p, x, positions, use_rope: bool, prefix: str = "",
         theta: float = 0.0):
    pre = prefix + "/" if prefix else ""
    b, t, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p[f"{pre}wq"].astype(x.dtype)).reshape(b, t, h, dh)
    k = (x @ p[f"{pre}wk"].astype(x.dtype)).reshape(b, t, kv, dh)
    v = (x @ p[f"{pre}wv"].astype(x.dtype)).reshape(b, t, kv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p[f"{pre}q_norm/scale"])
        k = rms_norm(k, p[f"{pre}k_norm/scale"])
    if use_rope and positions is not None:
        th = theta or cfg.rope_theta
        q = apply_rope(q, positions, th)
        k = apply_rope(k, positions, th)
    return q, k, v


def sdpa(q, k, v, mask, scale: float, attn_cap: float = 0.0):
    """q: (b, tq, h, dh); k, v: (b, tk, kv, dh); mask: (b?, tq, tk) bool."""
    b, tq, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, tq, kvh, g, dh)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                        preferred_element_type=_score_dtype(q)
                        ).astype(jnp.float32) * scale
    scores = softcap(scores, attn_cap)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, tq, h, dh).astype(q.dtype)


def _pick_chunk(t: int) -> int:
    for c in (2048, 1024, 512, 256, 128):
        if t % c == 0 and t > c:
            return c
    return t


def attention_prefill(cfg, spec, q, k, v):
    """Causal self-attention over a full sequence (train / prefill).

    Query-chunked scan; sliding windows slice the key band instead of
    scanning all keys (compute matches the window, not the sequence).
    """
    b, t, h, dh = q.shape
    scale = 1.0 / np.sqrt(dh)
    window = spec.window

    if (FLASH_KERNEL and spec.attn != ATTN_CHUNKED
            and t % 128 == 0 and dh % 8 == 0):
        from repro.kernels.flash_attention import flash_attention
        return flash_attention(
            q, k, v, causal=True,
            window=window if spec.attn == ATTN_SLIDING else 0,
            softcap=cfg.attn_softcap,
            interpret=FLASH_KERNEL == "pallas_interpret")

    if spec.attn == ATTN_CHUNKED and window and t % window == 0 and t > window:
        # block-diagonal: reshape into (chunks, window) and attend per chunk
        nc = t // window
        qc = q.reshape(b * nc, window, h, dh)
        kc = k.reshape(b * nc, window, k.shape[2], dh)
        vc = v.reshape(b * nc, window, v.shape[2], dh)
        pos = jnp.arange(window)
        mask = pos[:, None] >= pos[None, :]
        out = sdpa(qc, kc, vc, mask, scale, cfg.attn_softcap)
        return out.reshape(b, t, h, dh)

    cq = _pick_chunk(t)
    if cq == t:
        pos = jnp.arange(t)
        mask = pos[:, None] >= pos[None, :]
        if spec.attn in (ATTN_SLIDING, ATTN_CHUNKED) and window:
            if spec.attn == ATTN_SLIDING:
                mask &= pos[None, :] > pos[:, None] - window
            else:  # chunked, non-divisible small case
                mask &= (pos[:, None] // window) == (pos[None, :] // window)
        return sdpa(q, k, v, mask, scale, cfg.attn_softcap)

    nchunks = t // cq
    if spec.attn == ATTN_SLIDING and window:
        # pad keys in front by ceil(window/cq)*cq so each query chunk sees a
        # static band [c0 - band + cq, c0 + cq)
        band = int(np.ceil(window / cq)) * cq + cq
        pad = band - cq
        kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))

        def step(_, idx):
            c0 = idx * cq
            qs = jax.lax.dynamic_slice_in_dim(q, c0, cq, axis=1)
            ks = jax.lax.dynamic_slice_in_dim(kp, c0, band, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vp, c0, band, axis=1)
            qpos = c0 + jnp.arange(cq)
            kpos = c0 - pad + jnp.arange(band)
            mask = ((qpos[:, None] >= kpos[None, :])
                    & (kpos[None, :] > qpos[:, None] - window)
                    & (kpos[None, :] >= 0))
            return None, sdpa(qs, ks, vs, mask, scale, cfg.attn_softcap)

        _, outs = jax.lax.scan(step, None, jnp.arange(nchunks))
        return jnp.moveaxis(outs, 0, 1).reshape(b, t, h, dh)

    def step(_, idx):
        c0 = idx * cq
        qs = jax.lax.dynamic_slice_in_dim(q, c0, cq, axis=1)
        qpos = c0 + jnp.arange(cq)
        kpos = jnp.arange(t)
        mask = qpos[:, None] >= kpos[None, :]
        return None, sdpa(qs, k, v, mask, scale, cfg.attn_softcap)

    _, outs = jax.lax.scan(step, None, jnp.arange(nchunks))
    return jnp.moveaxis(outs, 0, 1).reshape(b, t, h, dh)


# ---------------------------------------------------------------------------
# decode (single token, KV cache; ring buffer for windowed layers)
# ---------------------------------------------------------------------------

def cache_len(spec, max_seq: int) -> int:
    """Ring-buffer length for a layer's cache."""
    if spec.attn in (ATTN_SLIDING, ATTN_CHUNKED) and spec.window:
        return min(spec.window, max_seq)
    return max_seq


def init_attn_cache(cfg, spec, batch: int, max_seq: int, abstract: bool):
    s = cache_len(spec, max_seq)
    kvd = (batch, s, cfg.n_kv_heads, cfg.head_dim)
    dt = jnp.dtype(cfg.dtype)
    if abstract:
        return {"k": jax.ShapeDtypeStruct(kvd, dt),
                "v": jax.ShapeDtypeStruct(kvd, dt)}
    return {"k": jnp.zeros(kvd, dt), "v": jnp.zeros(kvd, dt)}


def attn_cache_axes(spec):
    # kv_heads shards over 'model' when divisible; otherwise head_dim takes
    # it (128 % 16 == 0 for every assigned arch) — decode caches at
    # batch=128 x 32k otherwise exceed per-device HBM (see EXPERIMENTS §Perf).
    return {"k": ("batch", "kv_seq", "kv_heads", "head_dim"),
            "v": ("batch", "kv_seq", "kv_heads", "head_dim")}


def write_attn_cache(cache, k_new, v_new, pos):
    """Write t_new tokens starting at absolute position ``pos`` (ring)."""
    s = cache["k"].shape[1]
    t_new = k_new.shape[1]
    if t_new >= s:
        # keep the last s positions, ring-aligned: token at absolute position
        # q must land in slot q mod s.
        start = pos + t_new - s  # absolute position of the first kept token
        return {"k": jnp.roll(k_new[:, -s:], start, axis=1),
                "v": jnp.roll(v_new[:, -s:], start, axis=1)}
    slot = jnp.mod(pos, s)
    # dynamic_update_slice with wrap-around: do it in (up to) two writes via
    # roll — roll cache so that slot becomes 0, write at 0, roll back.
    def wr(buf, new):
        buf = jnp.roll(buf, -slot, axis=1)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, new, 0, axis=1)
        return jnp.roll(buf, slot, axis=1)
    return {"k": wr(cache["k"], k_new), "v": wr(cache["v"], v_new)}


def ring_positions(s: int, cur_pos):
    """Absolute position held by each ring slot once ``cur_pos`` tokens have
    been written. Slot j holds the largest q < cur_pos with q ≡ j (mod s);
    negative => never written."""
    j = jnp.arange(s)
    last = cur_pos - 1
    return last - jnp.mod(last - j, s)


def attention_decode(cfg, spec, q, cache, cur_pos):
    """q: (b, 1, h, dh); cache k/v: (b, s, kv, dh); cur_pos: scalar = number
    of tokens already in the cache (the query's absolute position)."""
    s = cache["k"].shape[1]
    scale = 1.0 / np.sqrt(cfg.head_dim)
    kv_pos = ring_positions(s, cur_pos + 1)  # includes the just-written token
    valid = (kv_pos >= 0) & (kv_pos <= cur_pos)
    if spec.attn == ATTN_SLIDING and spec.window:
        valid &= kv_pos > cur_pos - spec.window
    elif spec.attn == ATTN_CHUNKED and spec.window:
        valid &= (kv_pos // spec.window) == (cur_pos // spec.window)
    mask = valid[None, None, :]  # (1, tq=1, s)
    return sdpa(q, cache["k"], cache["v"], mask, scale, cfg.attn_softcap)


# ---------------------------------------------------------------------------
# full layer-level entry points
# ---------------------------------------------------------------------------

def attn_block_prefill(cfg, spec, p, x, positions, prefix: str = "",
                       cache=None, write_pos=0):
    """Returns (out, new_cache). positions: (t,) absolute positions."""
    pre = prefix + "/" if prefix else ""
    q, k, v = _qkv(cfg, p, x, positions, spec.use_rope, prefix,
                   theta=spec.rope_theta)
    out = attention_prefill(cfg, spec, q, k, v)
    new_cache = None
    if cache is not None:
        new_cache = write_attn_cache(cache, k, v, write_pos)
    b, t = x.shape[:2]
    out = out.reshape(b, t, -1) @ p[f"{pre}wo"].astype(x.dtype)
    return out, new_cache


def attn_block_decode(cfg, spec, p, x, cur_pos, cache, prefix: str = ""):
    """x: (b, 1, d). Writes the new token into the ring, then attends."""
    pre = prefix + "/" if prefix else ""
    positions = jnp.full((1,), cur_pos, dtype=jnp.int32)
    q, k, v = _qkv(cfg, p, x, positions, spec.use_rope, prefix,
                   theta=spec.rope_theta)
    cache = write_attn_cache(cache, k, v, cur_pos)
    out = attention_decode(cfg, spec, q, cache, cur_pos)
    b = x.shape[0]
    out = out.reshape(b, 1, -1) @ p[f"{pre}wo"].astype(x.dtype)
    return out, cache
