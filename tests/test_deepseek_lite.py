"""DeepSeek-V2-Lite against its plain float32 reference, on the CPU at the
``reduced()`` size with seeded random weights: MLA without query LoRA,
YaRN rope, the held-experts MoE layer with un-renormalised top-k weights and
the sequence-wise balance loss.

The reference is the benchmark's (``bench/configs/deepseek-v2-lite-ep8.py``,
its rehearsal block giving the ``reduced()`` sizes). Both sides compute in
float32 here, so the tolerances are float32 round-off of sums taken in a
different order (1e-5 relative on a loss of ~6, 1e-4 of a gradient leaf's
norm); a lower precision or a missing term is off by 1e-3 or more.
"""
import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import FedConfig
from repro.fed import make_algorithm
from repro.models import moe as moe_mod
from repro.models.layers import apply_rope, rope_freqs, yarn_mscale
from repro.models.mla import _softmax_scale
from repro.models.model import init_lm, lm_loss

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.precision import einsum_at  # noqa: E402
from bench.systems.spmd_lm_moe import model_config  # noqa: E402

FILE = ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8.json"
REF = harness.load_module(ROOT / "bench" / "configs" /
                          "deepseek-v2-lite-ep8.py", "dsv2lite_reference")
EIN = einsum_at("f32")


def _cfg(**kw):
    """The configuration file at its rehearsal (``reduced()``) size."""
    doc = harness._rehearsal(json.loads(FILE.read_text()))
    return {**doc, **kw}


def _tokens(cfg, b=2, t=32, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, t), 0,
                              cfg["vocab_size"])


def _ref_loss(cfg, params, toks):
    b, t = toks.shape
    rows = jax.vmap(lambda r: REF.row_loss(cfg, params, r, EIN))(toks)
    return jnp.sum(rows) / (b * (t - 1))


def _full():
    from repro.configs import get_config
    return get_config("deepseek-v2-lite")


def test_configuration_file_is_the_registered_model():
    """The benchmark's file at its rehearsal size is ``reduced()``; at its
    own size it is ``config()`` but for the cuts it lists: the depth, the
    experts held and the vocabulary slice. The file adds the deployment's
    training rule, DeepSeek-V2's device budget at capacity 1.0."""
    got = model_config(_cfg())
    want = get_reduced("deepseek-v2-lite")
    assert got.moe.device_capacity == 1.0
    assert got.replace(name=want.name, source=want.source, moe=dataclasses.
                       replace(got.moe, device_capacity=0.0)) == want
    full, want = model_config(json.loads(FILE.read_text())), _full()
    assert (full.n_layers, full.vocab_size, full.moe.held) == (5, 12_800, 8)
    assert full.replace(
        n_layers=want.n_layers, vocab_size=want.vocab_size,
        moe=dataclasses.replace(full.moe, n_held=0, device_capacity=0.0),
        name=want.name, source=want.source) == want


def test_reference_shapes_are_the_programs():
    cfg = _cfg()
    params, _ = init_lm(model_config(cfg), jax.random.PRNGKey(0))
    assert {k: tuple(v.shape) for k, v in params.items()} == REF.shapes(cfg)


def test_loss_and_gradients_match_the_reference():
    """MLA with no query LoRA and YaRN on, a dense layer then an MoE layer
    holding experts 2-3 of 4: the program's objective (mean cross-entropy
    plus the balance loss) and its gradient, leaf by leaf."""
    cfg = _cfg()
    mcfg = model_config(cfg)
    assert mcfg.mla.q_lora_rank == 0 and mcfg.rope_scaling is not None
    params = REF.weights(cfg, jax.random.PRNGKey(7))
    # the norms start at w = 0; move them so that 1 + w is no identity
    params = {k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
              if k.endswith("/scale") else v
              for i, (k, v) in enumerate(sorted(params.items()))}
    toks = _tokens(cfg)
    (loss, met), g = jax.value_and_grad(
        lambda p: lm_loss(mcfg, p, {"tokens": toks}), has_aux=True)(params)
    ref, g_ref = jax.value_and_grad(lambda p: _ref_loss(cfg, p, toks))(
        params)
    assert float(met["aux"]) > 0
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
    for k in params:
        err = float(jnp.linalg.norm(g[k] - g_ref[k]))
        assert err <= 1e-4 * float(jnp.linalg.norm(g_ref[k])) + 1e-9, k


def _moe_cfg(E=8, held=0, off=0, k=2, norm=False, budget=0.0):
    base = get_reduced("deepseek-v2-lite")
    return base.replace(moe=dataclasses.replace(
        base.moe, n_experts=E, top_k=k, n_held=held, held_offset=off,
        norm_topk_prob=norm, device_capacity=budget))


def _moe_params(cfg, key):
    m, d = cfg.moe, cfg.d_model
    ks = jax.random.split(key, 7)
    n = lambda i, s: jax.random.normal(ks[i], s) / math.sqrt(s[-2])  # noqa
    fs = m.d_ff_shared
    return {"router": n(0, (d, m.n_experts)),
            "w_gate": n(1, (m.n_experts, d, m.d_ff_expert)),
            "w_up": n(2, (m.n_experts, d, m.d_ff_expert)),
            "w_down": n(3, (m.n_experts, m.d_ff_expert, d)),
            "shared/w_gate": n(4, (d, fs)), "shared/w_up": n(5, (d, fs)),
            "shared/w_down": n(6, (fs, d))}


def _ref_moe(cfg, p, x):
    """The reference's layer for the experts ``cfg`` holds, whose weights
    ``p`` gives."""
    m = cfg.moe
    rc = {"n_routed_experts": m.n_experts, "num_experts_per_tok": m.top_k,
          "n_routed_experts_held": m.held,
          "n_routed_experts_offset": m.held_offset,
          "norm_topk_prob": m.norm_topk_prob, "routed_scaling_factor": 1,
          "aux_loss_alpha": m.router_aux_coef,
          "device_capacity_factor": m.device_capacity}
    dims = {"E": m.n_experts, "k": m.top_k, "held": m.held,
            "off": m.held_offset}
    rp = {f"moe/{k}": v for k, v in p.items()}
    return jax.vmap(lambda r: REF._moe(rc, dims, rp, r, EIN))(x)


def test_expert_shares_add_up_to_the_uncut_layer():
    """E = 8 experts over 4 shares of 2: the shares' outputs, with the
    shared experts (which every share computes alike) counted once, add
    up to the uncut reference layer; each share routes over all 8. No
    device budget: a share keeps every pair routed to it."""
    cfg = _moe_cfg()
    p = _moe_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    parts, routed = [], 0.0
    for s in range(4):
        cs = _moe_cfg(held=2, off=2 * s)
        ps = {k: (v[2 * s:2 * s + 2] if k in ("w_gate", "w_up", "w_down")
                  else v) for k, v in p.items()}
        out, aux = moe_mod.apply_moe(cs, ps, x)
        parts.append(out)
        routed += float(aux["routed_rows"])
        assert (float(aux["buffer_rows"]) == float(aux["kept_rows"])
                == float(aux["routed_rows"]))
    shared = jax.vmap(lambda r: REF._swiglu(EIN, r, p["shared/w_gate"],
                                            p["shared/w_up"],
                                            p["shared/w_down"]))(x)
    total = sum(parts) - 3 * shared
    ref, balance = _ref_moe(cfg, p, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert routed == 2 * 16 * 2            # every pair lands in one share
    whole, aux = moe_mod.apply_moe(cfg, p, x)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # the balance loss is over all 8 experts, from the full router
    np.testing.assert_allclose(float(aux["balance"]),
                               float(jnp.mean(balance)), rtol=1e-6)


def test_topk_weights_renormalised_or_not():
    """``norm_topk_prob`` false keeps the top-k softmax scores as they are
    (DeepSeek-V2-Lite); true divides them by their sum."""
    raw, norm = _moe_cfg(norm=False), _moe_cfg(norm=True)
    p = _moe_params(raw, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (32, raw.d_model))
    w0, i0, _ = moe_mod._router(raw, p, x, "", 2)
    w1, i1, _ = moe_mod._router(norm, p, x, "", 2)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    assert float(jnp.max(jnp.sum(w0, -1))) < 1.0
    np.testing.assert_allclose(np.asarray(w0 / jnp.sum(w0, -1, keepdims=True)),
                               np.asarray(w1), rtol=1e-6)
    for c in (raw, norm):
        out, _ = moe_mod.apply_moe(c, p, x[None])
        ref, _ = _ref_moe(c, p, x[None])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_device_budget_keeps_the_highest_weight_pairs():
    """DeepSeek-V2's device budget at capacity 1.0, a share of 2 of 8
    experts, top-2, 4 sequences of 16 tokens: each sequence may send 16 x
    2 x 2 / 8 = 8 pairs here. The layer matches the reference, which keeps
    each sequence's 8 pairs of highest weight; the inputs are such that
    some sequences drop pairs and others leave rows empty, and the grouped
    matmuls compute all 4 x 8 rows either way. Gradients as in
    ``test_loss_and_gradients_match_the_reference``."""
    cfg = _moe_cfg(held=2, off=2, budget=1.0)
    full = _moe_params(_moe_cfg(), jax.random.PRNGKey(8))
    p = {k: (v[2:4] if k in ("w_gate", "w_up", "w_down") else v)
         for k, v in full.items()}
    x = jax.random.normal(jax.random.PRNGKey(10), (4, 16, cfg.d_model))
    out, aux = moe_mod.apply_moe(cfg, p, x)
    ref, _ = _ref_moe(cfg, p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    routed, kept = float(aux["routed_rows"]), float(aux["kept_rows"])
    assert float(aux["buffer_rows"]) == 32 and kept < min(routed, 32)
    w, idx, _ = moe_mod._router(cfg, p, x.reshape(64, -1), "", 4)
    mine = ((idx >= 2) & (idx < 4)).reshape(4, 32).sum(1)
    assert int(jnp.max(mine)) > 8 > int(jnp.min(mine))
    _, _, sizes, _ = moe_mod._dispatch(cfg.moe, w, idx, 4)
    assert int(jnp.sum(sizes)) == 32
    # gradients flow through the kept pairs alone, as in the reference
    r = jax.random.normal(jax.random.PRNGKey(12), x.shape)
    g = jax.grad(lambda p, x: jnp.sum(moe_mod.apply_moe(cfg, p, x)[0] * r),
                 (0, 1))(p, x)
    g_ref = jax.grad(lambda p, x: jnp.sum(_ref_moe(cfg, p, x)[0] * r),
                     (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b))
    # without the budget every pair routed here is kept, and only they are
    # computed
    free = _moe_cfg(held=2, off=2)
    out, aux = moe_mod.apply_moe(free, p, x)
    assert (float(aux["buffer_rows"]) == float(aux["kept_rows"])
            == float(aux["routed_rows"]) == routed)
    _, _, sizes, _ = moe_mod._dispatch(free.moe, w, idx, 1)
    assert int(jnp.sum(sizes)) == routed
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref_moe(free, p, x)[0]),
                               rtol=1e-5, atol=1e-5)


def test_router_logits_in_float32():
    """``router_f32`` (DeepSeek-V2's gate) scores bfloat16 activations
    against the float32 router in float32; without it the product is taken
    in the activations' dtype."""
    cfg = _moe_cfg()
    p = _moe_params(cfg, jax.random.PRNGKey(10))
    x = jax.random.normal(jax.random.PRNGKey(11), (64, cfg.d_model)
                          ).astype(jnp.bfloat16)
    exact = jnp.dot(x.astype(jnp.float32), p["router"],
                    precision=jax.lax.Precision.HIGHEST)
    w, idx, _ = moe_mod._router(cfg, p, x, "", 1)
    want_w, want_i = jax.lax.top_k(jax.nn.softmax(exact, -1), 2)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), rtol=1e-6)
    off = cfg.replace(moe=dataclasses.replace(cfg.moe, router_f32=False))
    w16, _, _ = moe_mod._router(off, p, x, "", 1)
    assert float(jnp.max(jnp.abs(w16 - want_w))) > 1e-4


def test_yarn_frequencies_and_mscale():
    """DeepSeek-V2-Lite's rope (64 dims, theta 1e4, factor 40 from 4096
    positions, beta 32/1): the program's frequencies against the
    reference's float64 formula; the correction range 10-23; the softmax
    scale 192^-0.5 (0.1 * 0.707 * ln 40 + 1)^2."""
    full = _full()
    s = full.rope_scaling
    got = rope_freqs(64, full.rope_theta, s)
    want, cs, scale = REF.yarn(json.loads(FILE.read_text()))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 1.0 / 10_000.0 ** (np.arange(32) * 2 / 64)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all((got[10:23] < plain[10:23]) & (got[10:23] > plain[10:23] / 40))
    m = (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert yarn_mscale(40, 0.707) ** 2 == pytest.approx(m, rel=1e-12)
    assert m == pytest.approx(1.5896, abs=1e-4)
    assert _softmax_scale(full) == pytest.approx(192 ** -0.5 * m, rel=1e-12)
    assert float(cs) == 1.0 and float(scale) == pytest.approx(
        _softmax_scale(full), rel=1e-6)


def _old_apply_rope(x, positions, theta):
    """The rope as it was before YaRN, pinned."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd // 2, dtype=np.float32)
                             * 2.0 / hd))
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _old_router(m, router, x):
    """The Switch-style router as it was, pinned."""
    probs = jax.nn.softmax((x @ router.astype(x.dtype)).astype(jnp.float32),
                           axis=-1)
    weights, idx = jax.lax.top_k(probs, m.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    one_hot = jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32)
    aux = (m.n_experts * jnp.sum(jnp.mean(jnp.sum(one_hot, 1), 0)
                                 * jnp.mean(probs, 0)) * m.router_aux_coef)
    return weights.astype(x.dtype), idx, aux


@pytest.mark.parametrize("arch", ["llama4-scout-17b-a16e", "deepseek-v2-236b",
                                  "jamba-1.5-large-398b", "olmo-1b"])
def test_existing_configs_rope_and_router_unchanged(arch):
    """Configs without rope scaling or the new routing options compute
    what they did, bit for bit: the rope, the router's weights, picks and
    balance loss, and the MoE layer's output."""
    cfg = get_reduced(arch)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.n_heads,
                                                  cfg.head_dim))
    pos = jnp.arange(16)
    for theta in (cfg.rope_theta, 500_000.0):
        np.testing.assert_array_equal(
            np.asarray(apply_rope(x, pos, theta)),
            np.asarray(_old_apply_rope(x, pos, theta)))
    if cfg.moe is None:
        return
    params, _ = init_lm(cfg, jax.random.PRNGKey(1))
    pre = next(k[:-len("router")] for k in params if k.endswith("router"))
    p = {k[len(pre):]: v[0] if k.startswith("body/") else v
         for k, v in params.items() if k.startswith(pre)}
    h = jax.random.normal(jax.random.PRNGKey(2), (32, cfg.d_model))
    got = moe_mod._router(cfg, p, h, "", 2)
    for a, b in zip(got, _old_router(cfg.moe, p["router"], h)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    w, idx, _ = got
    out, n, kept, rows = moe_mod._moe_ragged(cfg, p, h, w, idx, "")
    k = cfg.moe.top_k
    order = jnp.argsort(idx.reshape(-1))
    xs = jnp.repeat(h, k, axis=0)[order]
    gs = jnp.bincount(idx.reshape(-1), length=cfg.moe.n_experts).astype(
        jnp.int32)
    y = jax.lax.ragged_dot(
        jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], gs))
        * jax.lax.ragged_dot(xs, p["w_up"], gs), p["w_down"], gs)
    old = jnp.sum(y[jnp.argsort(order)].reshape(32, k, -1) * w[..., None], 1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(old))
    assert int(n) == int(kept) == rows == 32 * k


def test_round_counts_routed_and_buffer_rows():
    """The spmd round of the reduced model reports, per local step, the
    pairs routed to the held experts, those kept, and the rows the grouped
    matmuls compute (one MoE layer, 2 sequences of 32 tokens, a budget of
    32 x 2 x 2 / 4 = 32 rows each); its local steps name their MLA and MoE
    blocks; a dense model reports no counter."""
    import re

    from repro.utils.spans import LOCAL_STEPS, MLA, MOE
    cfg = model_config(_cfg())
    fed = FedConfig(n_clients=1, s=1, local_steps=2, lr=0.05, bits=8,
                    kernel_backend="jnp")
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    alg = make_algorithm("spmd", fed, loss_fn=None, template=params,
                         batch_fn=None, cfg=cfg, batch=2, seq=32)
    data = {"tokens": _tokens({"vocab_size": cfg.vocab_size}, b=8)[None]}
    state, m = alg.round(alg.init(params), data, jax.random.PRNGKey(1))
    assert m["moe_buffer_rows"].shape == (2,)
    np.testing.assert_array_equal(np.asarray(m["moe_buffer_rows"]), 64.0)
    routed, kept = (np.asarray(m[k]) for k in ("moe_routed_rows",
                                                "moe_kept_rows"))
    assert np.all((0 < routed) & (routed <= 128))
    np.testing.assert_array_equal(kept, np.minimum(kept, np.minimum(routed,
                                                                     64)))
    assert np.all(kept > 0)
    text = type(alg)._round.lower(alg, alg.init(params), data,
                                  jax.random.PRNGKey(1)).compile().as_text()
    # whole paths only (the reducers of ``reduce`` ops carry a relative one)
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if n.startswith("jit(")]
    for scope in (MLA, MOE):
        inner = [n for n in names if f"{scope}/" in n]
        assert inner and all(LOCAL_STEPS + "/" in n.split(scope)[0]
                             for n in inner), scope
    assert any("transpose(" in n and f"{MOE}" in n for n in names)
    dense = get_reduced("olmo-1b")
    p2, _ = init_lm(dense, jax.random.PRNGKey(0))
    alg2 = make_algorithm("spmd", fed, loss_fn=None, template=p2,
                          batch_fn=None, cfg=dense, batch=2, seq=32)
    _, m2 = alg2.round(alg2.init(p2), data, jax.random.PRNGKey(1))
    assert not set(moe_mod.MOE_COUNTERS) & set(m2)
