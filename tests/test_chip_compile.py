"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Interpret-mode tests cannot see what the chip's compiler refuses (block
tiling, memory spaces, casts Mosaic does not lower). These tests compile
each kernel for a v5e that is described, not attached: the TPU compiler
runs on the host and no chip is needed. Every compiled program must hold
the kernel (``tpu_custom_call``); nothing is executed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every pytest worker
imports every test file.
"""
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.opbudget import (EXCHANGE_BLOCKS, EXCHANGE_GRID_STEPS,
                                     exchange_grid_counts)
from repro.compression.codecs import LatticeCodec
from repro.compression.rotation import _signs, dither
from repro.kernels.exchange import (fused_decode, fused_encode, fused_rotate,
                                    quantize_codes, snap_codes)
from repro.kernels.flash_attention import flash_attention
from repro.utils.spans import NOISE

D = 1 << 20             # 64 Hadamard blocks of 128 x 128


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile cache
    off (an entry written without a chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        cc.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _compile(fn, *shapes):
    """Compile ``fn`` for the shapes; returns the compiled HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _codes(sharding, m, pack):
    dt = jnp.uint8 if pack > 1 else jnp.uint32
    return jax.ShapeDtypeStruct((m, D // pack), dt, sharding=sharding)


# (m, pack, levels row): b=8 unpacked and b=4 packed two per byte, one or
# four messages per call, and one grouped-codec call with per-message moduli
CASES = [(1, 1, False), (1, 2, False), (4, 1, False), (4, 2, False),
         (4, 2, True)]
CASE_IDS = [f"m{m}-pack{p}" + ("-levels" if lv else "")
            for m, p, lv in CASES]


def _wire(m, pack, levels, sh):
    bits = 8 // pack
    extra = [_f32(sh, m)] if levels else []
    return bits, extra


@pytest.mark.parametrize("m,pack,levels", CASES, ids=CASE_IDS)
def test_fused_encode_compiles(one_chip, m, pack, levels):
    bits, extra = _wire(m, pack, levels, one_chip)

    def f(x, s, u, g, *lv):
        return fused_encode(x, s, u, g, bits=bits, pack=pack,
                            want_rotated=True, interpret=False,
                            levels2=lv[0] if lv else None)

    text = _compile(f, _f32(one_chip, m, D), _f32(one_chip, D),
                    _f32(one_chip, m, D), _f32(one_chip, m), *extra)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,pack,levels", CASES, ids=CASE_IDS)
def test_quantize_codes_compiles(one_chip, m, pack, levels):
    bits, extra = _wire(m, pack, levels, one_chip)

    def f(y, u, g, *lv):
        return quantize_codes(y, u, g, bits=bits, pack=pack, interpret=False,
                              levels2=lv[0] if lv else None)

    text = _compile(f, _f32(one_chip, m, D), _f32(one_chip, m, D),
                    _f32(one_chip, m), *extra)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,pack,levels", CASES, ids=CASE_IDS)
def test_snap_codes_compiles(one_chip, m, pack, levels):
    bits, extra = _wire(m, pack, levels, one_chip)

    def f(c, w, g, *lv):
        return snap_codes(c, w, g, bits=bits, pack=pack, interpret=False,
                          levels2=lv[0] if lv else None)

    # one shared rotated reference broadcast over the m messages
    text = _compile(f, _codes(one_chip, m, pack), _f32(one_chip, 1, D),
                    _f32(one_chip, m), *extra)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,pack,levels", CASES, ids=CASE_IDS)
def test_fused_decode_compiles(one_chip, m, pack, levels):
    bits, extra = _wire(m, pack, levels, one_chip)

    def f(c, r, s, g, *lv):
        return fused_decode(c, r, s, g, bits=bits, pack=pack, interpret=False,
                            levels2=lv[0] if lv else None)

    text = _compile(f, _codes(one_chip, m, pack), _f32(one_chip, 1, D),
                    _f32(one_chip, D), _f32(one_chip, m), *extra)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("inverse", [False, True])
def test_fused_rotate_compiles(one_chip, m, inverse):
    text = _compile(lambda x, s: fused_rotate(x, s, inverse=inverse,
                                              interpret=False),
                    _f32(one_chip, m, D), _f32(one_chip, D))
    assert "tpu_custom_call" in text


def test_vmapped_leaf_exchange_compiles(one_chip):
    """The mesh step encodes and decodes each leaf per client slot under
    ``vmap``, which adds a grid axis and a batch dim to every operand."""
    def f(x, s, u, g, r):
        codes = jax.vmap(lambda xi, ui, gi: fused_encode(
            xi, s, ui, gi, bits=8, interpret=False))(x, u, g)
        return jax.vmap(lambda ci, gi: fused_decode(
            ci, r, s, gi, bits=8, interpret=False))(codes, g)

    text = _compile(f, _f32(one_chip, 3, 1, D), _f32(one_chip, D),
                    _f32(one_chip, 3, 1, D), _f32(one_chip, 3, 1),
                    _f32(one_chip, 1, D))
    assert text.count("tpu_custom_call") >= 2


# the exchange kernels at an LM leaf's width, (m, pack, levels row) as
# in CASES; the MLP leaf of OLMo-1B's six layers: 6 x 2048 x 8192 weights,
# 6144 Hadamard blocks of 128 x 128
KERNELS = ["rotate", "encode", "encode-rotated", "quantize", "snap",
           "decode"]
WIRES = [(1, 1, False), (4, 2, True)]
OLMO_NB = 6 * 2048 * 8192 // (128 * 128)


def _kernel_call(kernel, m, pack, levels, nb, sharding=None):
    """(fn, abstract operands) of one exchange kernel call at nb blocks."""
    d = nb * 128 * 128
    bits = 8 // pack

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    codes = jax.ShapeDtypeStruct((m, d // pack),
                                 jnp.uint8 if pack > 1 else jnp.uint32,
                                 sharding=sharding)
    kw = dict(bits=bits, pack=pack, interpret=False)
    lv = [f32(m)] if levels else []
    if kernel == "rotate":
        return (lambda x, s: fused_rotate(x, s, interpret=False),
                [f32(m, d), f32(d)])
    if kernel.startswith("encode"):
        return (lambda x, s, u, g, *l: fused_encode(
            x, s, u, g, want_rotated=kernel == "encode-rotated",
            levels2=l[0] if l else None, **kw),
            [f32(m, d), f32(d), f32(m, d), f32(m)] + lv)
    if kernel == "quantize":
        return (lambda y, u, g, *l: quantize_codes(
            y, u, g, levels2=l[0] if l else None, **kw),
            [f32(m, d), f32(m, d), f32(m)] + lv)
    if kernel == "snap":
        return (lambda c, w, g, *l: snap_codes(
            c, w, g, levels2=l[0] if l else None, **kw),
            [codes, f32(1, d), f32(m)] + lv)
    return (lambda c, r, s, g, *l: fused_decode(
        c, r, s, g, levels2=l[0] if l else None, **kw),
        [codes, f32(1, d), f32(d), f32(m)] + lv)


def _blocks_a_step(fn, args, run):
    with exchange_grid_counts() as counts:
        out = run(fn, *args)
    steps = counts.get(EXCHANGE_GRID_STEPS)
    assert steps > 0
    return counts.get(EXCHANGE_BLOCKS) / steps, out


@pytest.mark.parametrize("m,pack,levels", WIRES,
                         ids=["m1-pack1", "m4-pack2-levels"])
@pytest.mark.parametrize("kernel", KERNELS)
def test_grouped_kernels_compile_at_olmo_leaf(one_chip, kernel, m, pack,
                                              levels):
    """At an OLMo MLP leaf's 6144 blocks each kernel compiles with the
    planned blocks a step, under the default scoped VMEM."""
    fn, args = _kernel_call(kernel, m, pack, levels, OLMO_NB, one_chip)
    g, text = _blocks_a_step(fn, args, _compile)
    assert g > 1
    assert "tpu_custom_call" in text


# blocks a step the plan gives at r = c = 128: the LM leaves' nb (OLMo's
# MLP and embedding stacks, its attention stack, DeepSeek's expert
# stacks), the MLP's message, odd counts
PLANNED = [(6144, 16), (6288, 16), (1536, 16), (5632, 16), (2, 2), (1, 1),
           (393, 1)]


@pytest.mark.parametrize("nb,g", PLANNED, ids=[f"nb{nb}" for nb, _ in PLANNED])
@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("m,pack,levels", WIRES,
                         ids=["m1-pack1", "m4-pack2-levels"])
def test_blocks_per_step_plan(kernel, m, pack, levels, nb, g):
    fn, args = _kernel_call(kernel, m, pack, levels, nb)
    assert _blocks_a_step(fn, args, jax.eval_shape)[0] == g


@pytest.mark.parametrize("pack,rotated", [(1, False), (1, True), (2, False),
                                          (2, True)])
def test_plan_never_takes_32_encode_blocks(pack, rotated):
    """32 f32 encode blocks of 128 x 128 overflow the v5e's 16 MiB scoped
    VMEM (the compiler reports 16.12M without the rotated output, 20.12M
    with it), however many blocks the leaf has."""
    d = (1 << 12) * 128 * 128
    x = jax.ShapeDtypeStruct((1, d), jnp.float32)
    enc = partial(fused_encode, bits=8 // pack, pack=pack,
                  want_rotated=rotated, interpret=False)
    g, _ = _blocks_a_step(enc, [x, jax.ShapeDtypeStruct((d,), jnp.float32),
                                x, jax.ShapeDtypeStruct((1,), jnp.float32)],
                          jax.eval_shape)
    assert 1 < g < 32


def test_vmapped_leaf_exchange_takes_groups_of_blocks():
    """The round's leaf exchange, traced at an OLMo MLP leaf (abstract
    shapes): each slot's encode and decode step over 8 or more blocks."""
    d = OLMO_NB * 128 * 128
    q = LatticeCodec(backend="pallas")
    keys = jax.ShapeDtypeStruct((1, 2), jnp.uint32)
    x = jax.ShapeDtypeStruct((1, d), jnp.float32)
    hint = jax.ShapeDtypeStruct((1,), jnp.float32)

    def exchange(k, x, h):
        msgs = jax.vmap(q.encode)(k, x, h)
        return jax.vmap(q.decode, in_axes=(0, 0, None))(k, msgs, x[0])

    g, _ = _blocks_a_step(exchange, [keys, x, hint], jax.eval_shape)
    assert g >= 8


def _noise_ops(text, result):
    """Lines of the compiled text in the noise scope whose result type
    starts with ``result`` (a regex)."""
    return [ln for ln in text.splitlines()
            if re.search(rf"= \(?{result}", ln) and NOISE in ln]


@pytest.mark.parametrize("what", ["draws", "encode"])
def test_vmapped_noise_draws_are_sublane_dense(one_chip, what):
    """Under a one-slot ``vmap`` a draw at (D,) becomes (1, D), which the
    TPU tiles T(1,128): one of a vreg's 8 sublanes. The signs and dither
    are drawn as (1, D/128, 128) instead, tiled T(8,128), and the encode
    kernel reads them as they are."""
    key = jax.ShapeDtypeStruct((1, 2), jnp.uint32, sharding=one_chip)
    if what == "draws":
        text = _compile(jax.vmap(lambda k: (dither(k, (D,)), _signs(k, D))),
                        key)
    else:
        q = LatticeCodec(backend="pallas")
        text = _compile(jax.vmap(q.encode), key, _f32(one_chip, 1, D),
                        _f32(one_chip, 1))
        assert "tpu_custom_call" in text
    assert not _noise_ops(text, rf"f32\[1,{D}\]\{{1,0:T\(1,128\)")
    assert _noise_ops(text, rf"f32\[1,{D // 128},128\]\{{2,1,0:T\(8,128\)")


def test_flash_attention_compiles_at_llama_heads(one_chip):
    """llama3.2-1b attention: 32 query heads, 8 KV heads of 64."""
    q = jax.ShapeDtypeStruct((1, 1024, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 1024, 8, 64), jnp.bfloat16,
                              sharding=one_chip)
    text = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                    interpret=False),
                    q, kv, kv)
    assert "tpu_custom_call" in text


def test_round_kernels_sit_in_the_exchange_scope(one_chip):
    """Every Mosaic kernel of a whole spmd round, compiled with the
    ``pallas`` backend, carries ``fl.exchange`` in its ``op_name``: the
    reduction of a chip trace counts kernel time under the exchange."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.configs import get_reduced
    from repro.configs.base import FedConfig
    from repro.fed import make_algorithm
    from repro.models.model import init_lm
    from repro.utils.spans import EXCHANGE

    device, = one_chip.device_set
    mesh = Mesh(np.array([device]).reshape(1, 1), ("data", "model"))
    cfg = get_reduced("llama3.2-1b")
    fed = FedConfig(n_clients=1, s=1, local_steps=2, lr=0.05, bits=8,
                    kernel_backend="pallas")
    template = jax.eval_shape(lambda: init_lm(cfg, jax.random.PRNGKey(0))[0])
    alg = make_algorithm("spmd", fed, loss_fn=None, template=template,
                         batch_fn=None, cfg=cfg, batch=2, seq=16, mesh=mesh)
    repl = NamedSharding(mesh, PartitionSpec())
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
        jax.eval_shape(alg.init, template))
    data = {"tokens": jax.ShapeDtypeStruct((1, 8, 16), jnp.int32,
                                           sharding=repl)}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
    text = type(alg)._round.lower(alg, state, data, key).compile().as_text()
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="([^"]*)"', text)
    assert names and len(names) == text.count(
        'custom_call_target="tpu_custom_call"')
    assert all(f"/{EXCHANGE}/" in n for n in names), names


def test_deepseek_lite_cell_round_fits_a_v5e(one_chip):
    """The `dsv2lite-ep8-train` round (DeepSeek-V2-Lite's widths, 1 dense
    + 4 MoE layers holding 8 of 64 experts, 4 x 2048 tokens a step, fp32
    server and client) compiled for one v5e chip: it leaves >= 2 GiB of
    the chip's 16 GiB, the depth rule of the benchmark's configurations.
    Its Mosaic kernels are the exchange's and the held experts' grouped
    matmuls (``ragged_dot`` lowers to them), so a metric that counts every
    ``tpu_custom_call`` as exchange time cannot read this cell."""
    import json
    import re
    import sys
    from pathlib import Path

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.fed import make_algorithm
    from repro.utils.spans import EXCHANGE, MOE

    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench import harness, traffic
    from bench.systems import spmd_lm, spmd_lm_moe

    device, = one_chip.device_set
    mesh = Mesh(np.array([device]).reshape(1, 1), ("data", "model"))
    spec = harness.load_spec()
    wl, entry = harness.resolve(spec, "dsv2lite-ep8-train")
    cfg = json.loads((root / entry["file"]).read_text())
    tr = traffic.load(wl["traffic"])
    model = harness.load_module(root / "bench" / "configs" /
                                f"{entry['name']}.py", "dsv2lite_shapes")
    template = jax.eval_shape(lambda: model.weights(cfg,
                                                    jax.random.PRNGKey(0)))
    seq = tr["inputs"]["seq"]
    alg = make_algorithm("spmd", spmd_lm.fed_config(cfg, tr), loss_fn=None,
                         template=template, batch_fn=None,
                         cfg=spmd_lm_moe.model_config(cfg), mesh=mesh,
                         batch=tr["batch"], seq=seq, remat=cfg["fed"]["remat"])
    repl = NamedSharding(mesh, PartitionSpec())
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
        jax.eval_shape(alg.init, template))
    data = {"tokens": jax.ShapeDtypeStruct((1, tr["inputs"]["pool"], seq),
                                           jnp.int32, sharding=repl)}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
    compiled = type(alg)._round.lower(alg, state, data, key).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    headroom = 16 * 2**30 - used
    print(f"dsv2lite-ep8-train round: {used / 1e9:.3f} GB, headroom "
          f"{headroom / 2**30:.3f} GiB")
    assert headroom >= 2 * 2**30
    text = compiled.as_text()
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="([^"]*)"', text)
    assert len(names) == text.count('custom_call_target="tpu_custom_call"')
    # the compiler names the grouped matmuls' kernels itself, with no
    # scope: a trace reads them as unscoped, by their own names
    experts = [n for n in names if f"/{EXCHANGE}/" not in n]
    assert experts and all(n.startswith("ragged-dot") for n in experts)
    assert f"/{MOE}/" in text
