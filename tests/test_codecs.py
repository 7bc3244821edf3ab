"""Composable codec/transport API: registry, round-trips, wire accounting,
bit-for-bit anchors, and per-algorithm composition.

Pins the api_redesign five ways:

  * codec registry round-trips: every registered codec encodes/decodes
    within its documented error bound, and the packed lattice wire is
    bit-identical math to the unpacked one at half the bytes,
  * wire accounting: ``message_bits`` reports what the interconnect moves
    (word-aligned uint codes vs. sub-byte packed vs. sparse pairs), and the
    algorithms' ``bits_up`` / ``bits_down`` metrics are computed by the
    codecs (METRIC_KEYS schema preserved),
  * the DEFAULT ``lattice`` codec reproduces the PR 3 traces bit for bit
    (golden fixture ``tests/golden_pr3.npz``, generated by the pre-redesign
    tree via ``tests/make_golden.py``),
  * every registry algorithm runs under ``simulate()`` with three distinct
    uplink codecs selected BY NAME only, and scanned == eager holds under
    each codec,
  * heterogeneous per-client bit budgets (grouped codecs) and error
    feedback (``topk_ef``) compose with the algorithms that thread them.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression import (GroupedLatticeCodec, LatticeWire,
                               make_codec, make_transport,
                               registered_codecs, registered_transports,
                               resolve_codec)
from repro.compression.codecs import _storage_bits
from repro.compression.pipeline import ExchangePipeline
from repro.compression.rotation import pad_len
from repro.configs.base import FedConfig
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch
from repro.fed import make_algorithm, registered_algorithms, simulate
from repro.kernels.exchange import pack_codes, unpack_codes
from repro.models.mlp import init_mlp_classifier, mlp_loss
from repro.utils.tree import tree_flatten_vector

from hypothesis import given, settings, strategies as st

CODEC_NAMES = ("lattice", "lattice_packed", "topk_ef", "scalar", "identity")


def _setup(fed, seed=0, d=16, hidden=32, classes=4):
    part, test = make_federated_classification(seed, fed.n_clients, d=d,
                                               n_classes=classes)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(seed), d, hidden,
                                     classes)
    bf = lambda dd, k: client_batch(k, dd, d)
    return part, test, params0, bf


# ---------------------------------------------------------------------------
# registry + per-codec round-trip error bounds
# ---------------------------------------------------------------------------

@pytest.mark.perf_smoke
def test_codec_registry_names():
    assert registered_codecs() == CODEC_NAMES
    assert registered_transports() == ("shard_local", "code_allgather",
                                       "reduce_scatter")
    with pytest.raises(ValueError):
        make_codec("huffman")
    with pytest.raises(ValueError):
        make_transport("carrier_pigeon")
    with pytest.raises(ValueError):
        make_codec("lattice:bogus=1")
    with pytest.raises(ValueError):
        make_codec("lattice_packed:bits=3")   # no whole codes per byte


@pytest.mark.perf_smoke
def test_every_registered_codec_steps_once():
    """CI gate: one encode->decode per registered codec, error within the
    documented bound, finite wire accounting."""
    d = 3000
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (d,))
    ref = x + 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (d,))
    hint = jnp.linalg.norm(x - ref)
    for spec, bound in (
            ("lattice", None),               # γ·sqrt(d_pad), msg-derived
            ("lattice_packed:bits=4", None),
            ("topk_ef:frac=0.05", float(hint) * 1.01),  # dropped support
            ("scalar", float(jnp.linalg.norm(x))),      # error ∝ ‖x‖
            ("identity", 1e-12)):
        codec = make_codec(spec)
        msg = codec.encode(key, x, hint)
        xh = codec.decode(key, msg, ref)
        err = float(jnp.linalg.norm(xh - x))
        if bound is None:
            bound = float(msg.gamma) * np.sqrt(pad_len(d)) * 1.01
        assert err <= bound, (spec, err, bound)
        assert 0 < codec.message_bits(d) < d * 64 + 64, spec
        assert codec.bits_per_coord(d) == pytest.approx(
            codec.message_bits(d) / d), spec


@settings(max_examples=10, deadline=None)
@given(bits=st.sampled_from([1, 2, 4, 8]), seed=st.integers(0, 1000))
def test_pack_unpack_roundtrip_property(bits, seed):
    d_pad = pad_len(3000)
    codes = jax.random.randint(jax.random.PRNGKey(seed), (2, d_pad), 0,
                               1 << bits).astype(jnp.uint32)
    packed = pack_codes(codes, bits=bits)
    assert packed.dtype == jnp.uint8
    assert packed.shape == (2, d_pad // (8 // bits))
    np.testing.assert_array_equal(np.asarray(unpack_codes(packed,
                                                          bits=bits)),
                                  np.asarray(codes))


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_packed_wire_is_bit_identical_math_at_half_bits(backend):
    """lattice vs lattice_packed at b=4: identical decode, half the wire."""
    d = 5000
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (d,))
    ref = x + 0.01 * jax.random.normal(jax.random.fold_in(key, 1), (d,))
    hint = jnp.linalg.norm(x - ref)
    un = make_codec("lattice:bits=4", backend=backend)
    pk = make_codec("lattice_packed:bits=4", backend=backend)
    m_un, m_pk = un.encode(key, x, hint), pk.encode(key, x, hint)
    assert m_pk.codes.dtype == jnp.uint8
    assert m_pk.codes.shape[0] * 2 == m_un.codes.shape[0]
    np.testing.assert_array_equal(
        np.asarray(un.decode(key, m_un, ref)),
        np.asarray(pk.decode(key, m_pk, ref)))
    # wire accounting: 4-bit packed vs uint8 unpacked = exactly half the
    # payload (+ the shared γ scalar)
    assert un.message_bits(d) - 32 == 2 * (pk.message_bits(d) - 32)
    # at b=8 the two codecs coincide
    assert make_codec("lattice").message_bits(d) == \
        make_codec("lattice_packed").message_bits(d)


def test_pipeline_round_packed_matches_unpacked():
    """Full rotated-space quafl_round under the packed wire: identical
    results (pack ∘ unpack is the identity inside the fused kernels)."""
    key = jax.random.PRNGKey(5)
    d, s = 5000, 4
    server = jax.random.normal(key, (d,))
    Y = server[None] + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 1), (s, d))
    hints = jnp.linalg.norm(Y - server[None], axis=1) + 1e-8
    for backend in ("jnp", "pallas_interpret"):
        pipe = ExchangePipeline(bits=4, backend=backend)
        out_u = pipe.quafl_round(key, server, Y, hints,
                                 up=LatticeWire(4), down=LatticeWire(4))
        out_p = pipe.quafl_round(key, server, Y, hints,
                                 up=LatticeWire(4, pack=2),
                                 down=LatticeWire(4, pack=2))
        np.testing.assert_array_equal(np.asarray(out_u[0]),
                                      np.asarray(out_p[0]))
        np.testing.assert_array_equal(np.asarray(out_u[1]),
                                      np.asarray(out_p[1]))


def test_topk_ef_error_feedback_recovers_residual():
    """With threaded state every coordinate is eventually transmitted: the
    residual of round t re-enters round t+1's top-k selection."""
    codec = make_codec("topk_ef:frac=0.5")
    key = jax.random.PRNGKey(0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=64), jnp.float32)
    state = codec.init_state(64)
    msg1, state = codec.encode_stateful(key, x, None, state)
    # round 2 with a ZERO input: the carried residual is what gets sent
    msg2, state = codec.encode_stateful(key, jnp.zeros(64), None, state)
    sent = set(np.asarray(msg1.idx)) | set(np.asarray(msg2.idx))
    assert len(sent) == 64          # k = 32 twice, disjoint via EF
    assert float(jnp.linalg.norm(state)) < 1e-6
    # stateless fallback: no residual, plain top-k of the input
    msg3 = codec.encode(key, x, None)
    assert set(np.asarray(msg3.idx)) == set(np.asarray(msg1.idx))


# ---------------------------------------------------------------------------
# bit-for-bit anchor: default lattice codec == PR 3 traces
# ---------------------------------------------------------------------------

GOLDEN_KW = {
    "quafl": {},
    "quafl_scaffold": {},
    "fedavg": {},
    "fedbuff_device": dict(buffer_size=2, uplink="lattice"),
}


@pytest.mark.parametrize("name", sorted(GOLDEN_KW))
def test_default_lattice_matches_pr3_golden(name):
    """The redesigned default path (lattice codec both directions, identity
    for FedAvg) reproduces the PR 3 slice bit for bit — server params AND
    the per-round bit counters. The fixture was re-recorded under jax
    0.9.0, whose random bits differ; tests/make_golden.py says how it was
    checked against the PR 3 file, which the test below still reads."""
    golden = np.load("tests/golden_pr3.npz")
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.3, bits=8)
    part, _, params0, bf = _setup(fed)
    alg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf, **GOLDEN_KW[name])
    state = alg.init(params0)
    key = jax.random.PRNGKey(7)
    ups, downs = [], []
    for _ in range(3):
        key, sub = jax.random.split(key)
        state, m = alg.round(state, part, sub)
        ups.append(float(m["bits_up"]))
        downs.append(float(m["bits_down"]))
    np.testing.assert_array_equal(
        np.asarray(tree_flatten_vector(alg.eval_params(state))),
        golden[f"{name}/server"])
    np.testing.assert_allclose(ups, golden[f"{name}/bits_up"])
    np.testing.assert_allclose(downs, golden[f"{name}/bits_down"])


@pytest.mark.parametrize("name", ["fedavg", "fedbuff_device", "quafl"])
def test_default_lattice_matches_pr3_jax04_golden(name):
    """The same slice against the file the PR 3 tree wrote under jax
    0.4.37, with that jax's random-bit layout (threefry not partitionable):
    the bit counters exactly, the server to fp32 rounding of the newer XLA.
    quafl_scaffold is left out: two of its codes land exactly on a floor
    boundary, where that rounding decides the code (tests/make_golden.py)."""
    golden = np.load("tests/golden_pr3_jax04.npz")
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.3, bits=8)
    with jax.threefry_partitionable(False):
        part, _, params0, bf = _setup(fed)
        alg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                             batch_fn=bf, **GOLDEN_KW[name])
        state = alg.init(params0)
        key = jax.random.PRNGKey(7)
        ups, downs = [], []
        for _ in range(3):
            key, sub = jax.random.split(key)
            state, m = alg.round(state, part, sub)
            ups.append(float(m["bits_up"]))
            downs.append(float(m["bits_down"]))
        server = np.asarray(tree_flatten_vector(alg.eval_params(state)))
    np.testing.assert_allclose(server, golden[f"{name}/server"], rtol=0,
                               atol=2.5e-7)
    np.testing.assert_array_equal(ups, golden[f"{name}/bits_up"])
    np.testing.assert_array_equal(downs, golden[f"{name}/bits_down"])


# ---------------------------------------------------------------------------
# composition: every algorithm × codecs by name, bits by the codec
# ---------------------------------------------------------------------------

MATRIX_CODECS = ("scalar", "identity", "topk_ef:frac=0.1")


@pytest.mark.parametrize("codec", MATRIX_CODECS)
def test_every_algorithm_runs_every_codec_by_name(codec):
    """Acceptance: each registry algorithm runs under simulate() with
    distinct uplink codecs selected by NAME only (FedConfig.codec_up — no
    algorithm-code changes), and the bits metrics are the codec's."""
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2, bits=8,
                    codec_up=codec)
    part, _, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    up = make_codec(codec)
    for name in registered_algorithms():
        if name == "spmd":     # needs a ModelConfig; covered below
            continue
        kw = {"buffer_size": 2} if "fedbuff" in name else {}
        alg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                             batch_fn=bf, **kw)
        tr = simulate(alg, params0, part, jax.random.PRNGKey(1), rounds=2,
                      eval_every=0)
        assert tr.rounds == 2, (name, codec)
        f = tr.final
        assert np.isfinite(f["bits_up"]) and np.isfinite(f["quant_err"])
        if name == "sequential":
            continue           # no communication: counters stay 0
        if name == "adaptive_quafl":
            continue           # the bit-walk changes the codec's width
        # bits computed BY the codec: per-message size divides the metric
        per = up.message_bits(alg.d)
        assert f["bits_up"] % per == 0 and f["bits_up"] > 0, (name, codec)


def test_spmd_runs_codecs_by_name():
    """The mesh-sharded train step resolves the same codec registry."""
    from repro.configs import get_reduced
    from repro.data.synthetic import federated_token_task
    from repro.models.model import init_lm, lm_loss
    from functools import partial
    cfg = get_reduced("llama3.2-1b")
    for codec in ("lattice", "scalar:bits=6", "identity"):
        fed = FedConfig(n_clients=1, s=1, local_steps=1, lr=0.02,
                        codec_up=codec, codec_down=codec)
        params0, _ = init_lm(cfg, jax.random.PRNGKey(0))
        data, batch_fn = federated_token_task(0, 1, 32, 2, 16,
                                              cfg.vocab_size)
        alg = make_algorithm("spmd", fed, loss_fn=partial(lm_loss, cfg),
                             template=params0, batch_fn=batch_fn, cfg=cfg,
                             batch=2, seq=16)
        st, m = alg.round(alg.init(params0), data, jax.random.PRNGKey(1))
        assert np.isfinite(float(m["bits_up"]))
        assert float(m["bits_up"]) == alg._bits_up_msg  # one client slot


@pytest.mark.parametrize("codec", ("lattice", "scalar", "identity",
                                   "lattice_packed:bits=4"))
def test_scanned_equals_eager_under_each_codec(codec):
    """The device-round/scan contract holds for every codec family (and
    the donated chunk entry changes nothing). Tolerance is PR 3's: XLA
    fuses a multi-round loop body differently than the standalone round,
    so params agree to float32 rounding; the bit counters are exact."""
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2, bits=8,
                    codec_up=codec, codec_down=codec)
    part, _, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    tr_e = simulate(alg, params0, part, jax.random.PRNGKey(2), rounds=6,
                    eval_every=0)
    tr_s = simulate(alg, params0, part, jax.random.PRNGKey(2), rounds=6,
                    eval_every=0, scan_chunk=3)
    assert tr_s.engine == "scanned"
    np.testing.assert_allclose(
        np.asarray(tree_flatten_vector(alg.eval_params(tr_e.final_state))),
        np.asarray(tree_flatten_vector(alg.eval_params(tr_s.final_state))),
        rtol=1e-6, atol=1e-7)
    assert [r["bits_up_total"] for r in tr_e.rows] == \
        [r["bits_up_total"] for r in tr_s.rows]


def test_compressed_fedavg_scanned_with_donation():
    """compressed_fedavg under the donated scanned engine: its state must
    not alias server/srv_prev buffers (XLA rejects donating one buffer
    twice), and EF residuals ride the scan carry."""
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2, bits=8,
                    codec_up="topk_ef:frac=0.1")
    part, _, params0, bf = _setup(fed, d=8, hidden=8, classes=2)
    alg = make_algorithm("compressed_fedavg", fed, loss_fn=mlp_loss,
                         template=params0, batch_fn=bf)
    tr_e = simulate(alg, params0, part, jax.random.PRNGKey(2), rounds=4,
                    eval_every=0)
    tr_s = simulate(alg, params0, part, jax.random.PRNGKey(2), rounds=4,
                    eval_every=0, scan_chunk=4)
    assert tr_s.engine == "scanned"
    np.testing.assert_allclose(
        np.asarray(tree_flatten_vector(alg.eval_params(tr_e.final_state))),
        np.asarray(tree_flatten_vector(alg.eval_params(tr_s.final_state))),
        rtol=1e-6, atol=1e-7)
    assert float(jnp.linalg.norm(tr_s.final_state.codec_up_state)) > 0


# ---------------------------------------------------------------------------
# heterogeneous per-client bit budgets (grouped codec)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_grouped_codec_equals_uniform_when_bits_match(backend):
    """A {"fast", "slow"} group map with EQUAL bits must be bit-for-bit the
    uniform codec (the per-message-levels path degenerates exactly) — on
    the Pallas backends the degenerate levels row rides the kernels."""
    fed = FedConfig(n_clients=6, s=3, local_steps=1, lr=0.2, bits=8,
                    kernel_backend=backend)
    part, _, params0, bf = _setup(fed)
    a_u = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf)
    a_g = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf, uplink={"fast": "lattice",
                                              "slow": "lattice"})
    assert isinstance(a_g.codec_up, GroupedLatticeCodec)
    su, sg = a_u.init(params0), a_g.init(params0)
    key = jax.random.PRNGKey(5)
    for _ in range(3):
        key, sub = jax.random.split(key)
        su, mu = a_u.round(su, part, sub)
        sg, mg = a_g.round(sg, part, sub)
    np.testing.assert_array_equal(np.asarray(su.server),
                                  np.asarray(sg.server))
    # the math degenerates exactly, but the grouped wire still ships its
    # per-message modulus row (32 bits/message) — charged, not exempted,
    # so the wire-truth audit can hold the levels side channel to account
    assert (float(mu["bits_up"]) + 32.0 * fed.s) == float(mg["bits_up"])


def test_grouped_codec_charges_per_client_bits():
    """Mixed bits: stragglers at b=4 are charged 4 bits/coord (their
    uniform-width message ships packed), fast clients 8 — the round's
    bits_up is the exact sum over the sampled subset."""
    fed = FedConfig(n_clients=6, s=6, local_steps=1, lr=0.2, bits=8,
                    slow_frac=0.5)
    part, _, params0, bf = _setup(fed)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=bf,
                         uplink={"fast": "lattice",
                                 "slow": "lattice_packed:bits=4"})
    assert alg.codec_up.bits_per_client == (4, 4, 4, 8, 8, 8)
    # wire accounting follows each MEMBER codec's declared format: the
    # packed b=4 stragglers charge 4 bits/coord, unpacked b=8 charges 8
    assert alg.codec_up.wire_width_per_client == (4, 4, 4, 8, 8, 8)
    st, m = alg.round(alg.init(params0), part, jax.random.PRNGKey(0))
    dp = pad_len(alg.d)
    # each message: payload + γ scalar + levels-row entry (32 + 32)
    expect = 3 * (dp * 4 + 64) + 3 * (dp * 8 + 64)   # s=n: all sampled
    assert float(m["bits_up"]) == expect
    assert np.isfinite(float(m["quant_err"]))
    # group codecs are uplink-only and need the speed classes
    with pytest.raises(ValueError):
        resolve_codec({"fast": "lattice"}, fed, direction="down",
                      default="lattice")
    with pytest.raises(ValueError):
        resolve_codec({"fast": "lattice"}, fed, direction="up",
                      default="lattice")


def test_grouped_codec_codes_bit_exact_across_backends():
    """The levels-row operand: per-message wrap moduli through the Pallas
    kernels give integer codes BIT-EXACT vs jnp and decoded params to
    float rounding; the sub-byte packed wire stays identical math under
    mixed moduli (every modulus fits the static bits container)."""
    from repro.compression.pipeline import get_backend
    from repro.compression.rotation import _signs
    key = jax.random.PRNGKey(7)
    s, d_pad = 4, pad_len(5000)
    Y = jax.random.normal(key, (s, d_pad))
    signs = _signs(jax.random.fold_in(key, 0), d_pad)
    u = jax.random.uniform(jax.random.fold_in(key, 1), (s, d_pad))
    gam = jnp.linspace(0.05, 0.2, s)
    ref = 0.01 * jax.random.normal(jax.random.fold_in(key, 2), (s, d_pad))
    bj, bp = get_backend("jnp"), get_backend("pallas_interpret")
    # unpacked, b=8 container, mixed moduli {16, 256, 64}
    lv8 = jnp.asarray([16.0, 256.0, 64.0, 16.0])
    cj = bj.encode(Y, signs, u, gam, bits=8, levels2=lv8)
    cp = bp.encode(Y, signs, u, gam, bits=8, levels2=lv8)
    np.testing.assert_array_equal(np.asarray(cj), np.asarray(cp))
    xj = bj.decode(cj, ref, signs, gam, bits=8, levels2=lv8)
    xp = bp.decode(cp, ref, signs, gam, bits=8, levels2=lv8)
    np.testing.assert_allclose(np.asarray(xj), np.asarray(xp),
                               rtol=2e-5, atol=2e-5)
    # packed b=4 container, mixed moduli {16, 8, 4}: packed == unpacked
    # on BOTH backends (pack keeps the static container width)
    lv4 = jnp.asarray([16.0, 8.0, 4.0, 16.0])
    for be in (bj, bp):
        cpk = be.encode(Y, signs, u, gam, bits=4, pack=2, levels2=lv4)
        cun = be.encode(Y, signs, u, gam, bits=4, pack=1, levels2=lv4)
        assert cpk.dtype == jnp.uint8 and cpk.shape[1] * 2 == cun.shape[1]
        wrot = be.rotate(ref, signs)
        np.testing.assert_array_equal(
            np.asarray(be.snap(cpk, wrot, gam, bits=4, pack=2,
                               levels2=lv4)),
            np.asarray(be.snap(cun, wrot, gam, bits=4, pack=1,
                               levels2=lv4)))


def test_grouped_codec_round_on_pallas_backend():
    """A grouped codec resolved on the pallas_interpret backend (formerly a
    NotImplementedError) runs full algorithm rounds and tracks the jnp
    backend to kernel float rounding."""
    servers = {}
    for backend in ("jnp", "pallas_interpret"):
        fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2, bits=8,
                        slow_frac=0.5, kernel_backend=backend)
        part, _, params0, bf = _setup(fed)
        alg = make_algorithm("quafl", fed, loss_fn=mlp_loss,
                             template=params0, batch_fn=bf,
                             uplink={"fast": "lattice",
                                     "slow": "lattice:bits=4"})
        assert isinstance(alg.codec_up, GroupedLatticeCodec)
        assert alg.codec_up.backend == backend
        st_a, m = alg.round(alg.init(params0), part, jax.random.PRNGKey(3))
        assert np.isfinite(float(m["quant_err"]))
        servers[backend] = np.asarray(
            tree_flatten_vector(alg.eval_params(st_a)))
    np.testing.assert_allclose(servers["pallas_interpret"], servers["jnp"],
                               rtol=2e-5, atol=2e-5)


def test_grouped_unpacked_members_charge_storage_width():
    """Unpacked b=4 members are honestly charged their uint8 storage (8
    bits/coord) — only packed members earn the sub-byte accounting."""
    fed = FedConfig(n_clients=4, s=2, bits=8)
    g = resolve_codec({"fast": "lattice", "slow": "lattice:bits=4"}, fed,
                      direction="up", default="lattice",
                      slow_mask=np.array([1, 1, 0, 0], bool))
    assert g.bits_per_client == (4, 4, 8, 8)
    assert g.wire_width_per_client == (8, 8, 8, 8)


# ---------------------------------------------------------------------------
# wire accounting sanity
# ---------------------------------------------------------------------------

def test_wire_accounting_table():
    d = 20000
    dp = pad_len(d)
    assert make_codec("identity").message_bits(d) == d * 32
    assert make_codec("scalar:bits=6").message_bits(d) == d * 6 + 32
    # unpacked lattice ships the uint dtype that holds the codes
    assert make_codec("lattice:bits=4").message_bits(d) == dp * 8 + 32
    assert make_codec("lattice:bits=12").message_bits(d) == dp * 16 + 32
    assert _storage_bits(12) == 16
    # packed lattice ships exactly b bits per (padded) coordinate
    assert make_codec("lattice_packed:bits=4").message_bits(d) == \
        dp * 4 + 32
    assert make_codec("lattice_packed:bits=1").message_bits(d) == dp + 32
    k = make_codec("topk_ef:frac=0.01").k_for(d)
    assert make_codec("topk_ef:frac=0.01").message_bits(d) == k * 64


def test_transport_extra_bits_down():
    """The gathered side-channel rows / coded re-gather each transport
    moves beyond the per-message codec math land in ``extra_bits_down``
    (charged into the spmd path's bits_down)."""
    up = make_codec("lattice")
    dn = make_codec("lattice_packed:bits=4")
    d, n = 65536, 4                     # 4 shards of 16384: shardable
    assert make_transport("shard_local").extra_bits_down(up, dn, d, n) == 0
    # code all-gather: every device receives the n-1 OTHER clients' γ f32
    ag = make_transport("code_allgather")
    assert ag.extra_bits_down(up, dn, d, n) == (n - 1) * 32
    # a grouped uplink gathers a second (levels) row per client
    fed = FedConfig(n_clients=4, s=2, bits=8)
    g = resolve_codec({"fast": "lattice", "slow": "lattice:bits=4"}, fed,
                      direction="up", default="lattice",
                      slow_mask=np.array([1, 1, 0, 0], bool))
    assert ag.extra_bits_down(g, dn, d, n) == 2 * (n - 1) * 32
    # fused reduce_scatter: one downlink-width coded message + n-1 γ shards
    rs = make_transport("reduce_scatter")
    assert rs.extra_bits_down(up, dn, d, n) == \
        dn.message_bits(d) + (n - 1) * 32
    # exact-psum fallbacks charge nothing: n=1, non-tiling d_pad, or a
    # generic codec pair without a lattice wire
    assert rs.extra_bits_down(up, dn, d, 1) == 0
    assert rs.extra_bits_down(up, dn, 3 * 16384, 4) == 0
    assert rs.extra_bits_down(up, make_codec("scalar:bits=6"), d, n) == 0
