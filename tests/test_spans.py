"""The round's layers carry their names (``repro.utils.spans``) into the
compiled program and into the profiler's trace, and naming them changes
nothing that is computed.

A device scope is a component of each op's ``op_name`` in the compiled HLO,
possibly inside transform wrappers (``vmap(fl.exchange.noise)``,
``transpose(jvp(...))``); the innermost scope on the path owns the op.
"""
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import FedConfig
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch, federated_token_task
from repro.fed import make_algorithm, simulate
from repro.fed.engine import RoundEngine
from repro.models.mlp import init_mlp_classifier, mlp_loss
from repro.models.model import init_lm
from repro.utils.spans import (CHUNK, EVAL, EXCHANGE, LOCAL_STEPS, NOISE,
                               POPULATION, ROUND, SCOPES, SYNC)

# an instruction of compiled HLO text: (opcode, op_name)
_INSTR = re.compile(
    r'^\s*(?:ROOT )?%\S+ = .*?\s([a-z][\w\-]*)\(.*?op_name="([^"]*)"', re.M)
_WRAP = re.compile(r"^\w+\((.*)\)$")


def _scopes(op_name):
    """The program scopes on an op's name-stack path, outermost first."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAP.match(part)):
            part = m.group(1)
        if part in SCOPES:
            out.append(part)
    return out


def _ops(hlo_text):
    """(opcode, op_name, scopes) of each instruction with a whole path
    (the reducers of ``reduce`` ops carry a relative one)."""
    return [(opc, name, _scopes(name))
            for opc, name in _INSTR.findall(hlo_text)
            if name.startswith("jit(")]


def _assert_exchange_and_steps_named(ops):
    """What every round shares: the sign draws are exchange noise, noise
    sits inside the exchange, the backward pass and the local-step loop
    are local steps."""
    assert {LOCAL_STEPS, EXCHANGE, NOISE} <= {s for *_, sc in ops
                                               for s in sc}
    signs = [sc for _, name, sc in ops if "jit(_rademacher)" in name]
    assert signs and all(sc[-1] == NOISE for sc in signs)
    for _, name, sc in ops:
        if NOISE in sc:
            assert EXCHANGE in sc[:sc.index(NOISE)], name
    grads = [sc for _, name, sc in ops if "transpose(" in name]
    assert grads and all(LOCAL_STEPS in sc for sc in grads)
    # the K-step loop itself: a while right inside the scope
    assert any(re.fullmatch(r"\)*(/vmap\(\))*/while",
                            name.rsplit(LOCAL_STEPS, 1)[-1])
               for opc, name, sc in ops
               if opc == "while" and sc[-1:] == [LOCAL_STEPS])


def _spmd(backend):
    cfg = get_reduced("llama3.2-1b")
    fed = FedConfig(n_clients=1, s=1, local_steps=2, lr=0.05, bits=8,
                    kernel_backend=backend)
    params0, _ = init_lm(cfg, jax.random.PRNGKey(0))
    data, bf = federated_token_task(0, 1, 8, 2, 16, cfg.vocab_size)
    alg = make_algorithm("spmd", fed, loss_fn=None, template=params0,
                         batch_fn=bf, cfg=cfg, batch=2, seq=16)
    return alg, params0, data


def _quafl(backend="jnp"):
    fed = FedConfig(n_clients=8, s=3, local_steps=2, lr=0.3, bits=8,
                    kernel_backend=backend)
    part, _ = make_federated_classification(0, fed.n_clients, d=8,
                                            n_classes=2, iid=False)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(0), 8, 8, 2)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=lambda dd, k: client_batch(k, dd, 8))
    return alg, params0, part


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_spmd_round_names_its_layers(backend):
    alg, params0, data = _spmd(backend)
    ops = _ops(type(alg)._round.lower(
        alg, alg.init(params0), data, jax.random.PRNGKey(1)
    ).compile().as_text())
    _assert_exchange_and_steps_named(ops)
    # the token draw of the local steps; no population store on the mesh
    assert any(LOCAL_STEPS in sc for _, name, sc in ops
               if "jit(_randint)" in name)
    assert not any(POPULATION in sc for *_, sc in ops)


def test_quafl_chunk_names_its_layers():
    alg, params0, part = _quafl()
    ops = _ops(RoundEngine(alg).lowered_chunk(
        alg.init(params0), part, jax.random.PRNGKey(1), 2
    ).compile().as_text())
    _assert_exchange_and_steps_named(ops)
    rows = [opc for opc, _, sc in ops if sc and sc[-1] == POPULATION]
    assert "gather" in rows and "scatter" in rows


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def _assert_same(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_spmd_round_is_the_same_under_the_profiler(tmp_path):
    alg, params0, data = _spmd("jnp")
    key = jax.random.PRNGKey(3)
    plain = alg.round(alg.init(params0), data, key)
    with jax.profiler.trace(str(tmp_path)):
        traced = alg.round(alg.init(params0), data, key)
        jax.block_until_ready(traced)
    _assert_same(plain, traced)
    assert ROUND in _host_spans(tmp_path)


def test_simulate_is_the_same_under_the_profiler(tmp_path):
    alg, params0, part = _quafl()

    def run():
        return simulate(alg, params0, part, jax.random.PRNGKey(4), rounds=4,
                        eval_every=2, record_every=1, scan_chunk=2,
                        eval_fn=lambda p: float(jax.tree_util.tree_leaves(
                            p)[0].sum()))

    plain = run()
    with jax.profiler.trace(str(tmp_path)):
        traced = run()
    _assert_same(plain.final_state, traced.final_state)
    assert [r["eval"] for r in plain.rows if "eval" in r] == \
        [r["eval"] for r in traced.rows if "eval" in r]
    assert {CHUNK, SYNC, EVAL} <= _host_spans(tmp_path)
