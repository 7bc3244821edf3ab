"""``python -m repro.analysis.lint`` — the repo's static-analysis gate.

Runs every half of :mod:`repro.analysis` and writes a machine-readable,
**deterministic** ``ANALYSIS.json`` (schema ``analysis.v2`` — byte-
identical across runs; wall-clock timings go to ``bench_out/``, not the
committed report):

* **jaxpr matrix** — every registry algorithm × {lattice, lattice_packed,
  topk_ef} uplink codec is built at a tiny config, its round and scanned
  chunk traced through :meth:`RoundEngine.traced_round` / ``traced_chunk``,
  and checked for host callbacks, wide dtypes, key discipline, the
  rotation op-budget, the donation contract of the compiled chunk — plus
  the PR 10 dataflow analyzers on the round trace: the wire-truth audit
  (:mod:`repro.analysis.wire`), γ-overflow interval analysis
  (:mod:`repro.analysis.intervals`) and SPMD divergence detection
  (:mod:`repro.analysis.divergence`). A scanned ``simulate()`` run per
  algorithm feeds the recompile sentinel.
* **exchange matrix** — every codec × transport pair of the shard-local
  exchange is traced on an abstract (4, 2) data×model mesh and audited
  against the transport's declared :class:`~repro.compression.transports.
  WireBudget`: wire-truth (every gathered payload marked + container-
  exact), per-collective byte caps, divergence escapes, and the
  reduce-scatter γ_rs wrap proof.
* **AST rules** — :func:`repro.analysis.astlint.lint_path` over
  ``src/repro/``.
* **rs transport byte budget** — the historical ``rs_transport_audit``
  cell, now budgeted by ``ReduceScatterSum.wire_budget`` instead of
  hand-pinned caps.

Exit status is the number of violations (0 = clean). Flags::

    --json PATH      where to write the report (default: repo-root
                     ANALYSIS.json; "-" to skip writing)
    --quick          skip the donation compiles and sentinel runs (the two
                     expensive passes) — trace-level + AST checks only
    --only SUBSTR    run only cells whose name contains SUBSTR (e.g.
                     --only quaflxlattice, --only exchange:). Unknown
                     selectors are a loud error listing every cell.
    --list           print every cell name the gate would run, then exit

Registering a new analyzer = writing a function returning
``List[Violation]`` and appending it in :func:`analyze_cell` /
:func:`analyze_exchange_cell` (jaxpr-level) or
:func:`repro.analysis.astlint.lint_source` (source-level); the README
"Static analysis" section walks through it.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List, Optional

# algorithm × codec matrix ---------------------------------------------------

MATRIX_CODECS = ("lattice", "lattice_packed", "topk_ef")

# codec × transport exchange matrix (abstract-mesh shard_map traces)
MATRIX_TRANSPORTS = ("shard_local", "code_allgather", "reduce_scatter")
_EXCHANGE_CODECS = ("lattice:bits=8", "lattice_packed:bits=4", "topk_ef")

# per-algorithm construction kwargs at the tiny lint config
_ALG_KWARGS = {"fedbuff_device": {"buffer_size": 2}}

# sparse EF uplink composes with every algorithm; the fused lattice
# downlink families also run the downlink direction
_DOWNLINK_OK = ("lattice", "lattice_packed")


def _cells(only: Optional[str] = None):
    from repro.fed.registry import registered_algorithms
    algs = [a for a in registered_algorithms() if a != "fedbuff"]
    for alg in algs:
        codecs = MATRIX_CODECS
        if alg == "quafl":
            # heterogeneous per-client widths: the batched exchange with a
            # levels row — the PR 9 side channel the wire audit must see
            codecs = codecs + ("lattice_grouped",)
        for codec in codecs:
            cell = f"{alg}x{codec}"
            if only and only not in cell:
                continue
            yield alg, codec


def _exchange_cell_name(codec: str, transport: str) -> str:
    return f"exchange:{codec.split(':')[0]}x{transport}"


def _exchange_cells(only: Optional[str] = None):
    for codec in _EXCHANGE_CODECS:
        for transport in MATRIX_TRANSPORTS:
            if only and only not in _exchange_cell_name(codec, transport):
                continue
            yield codec, transport


def list_cells() -> List[str]:
    """Every cell name the full gate runs (the ``--list`` surface)."""
    names = [f"{a}x{c}" for a, c in _cells()]
    names += [_exchange_cell_name(c, t) for c, t in _exchange_cells()]
    names += ["rs_transport"]
    names += [f"sentinel:{a}" for a, c in _cells() if c == "lattice"]
    return names


def _build_cell(alg_name: str, codec: str):
    """Build (alg, params0, data, key) at the tiny lint config."""
    import jax
    from repro.configs.base import FedConfig
    from repro.fed.registry import make_algorithm
    kw = dict(_ALG_KWARGS.get(alg_name, {}))
    if codec == "lattice_grouped":
        # dict specs resolve against the clock's straggler mask into ONE
        # GroupedLatticeCodec (mixed 8/4-bit member widths)
        kw["uplink"] = {"fast": "lattice", "slow": "lattice:bits=4"}
        codec, down = "", ""
    else:
        down = codec if codec.split(":")[0] in _DOWNLINK_OK else ""
    if alg_name == "spmd":
        from functools import partial
        from repro.configs import get_reduced
        from repro.data.synthetic import federated_token_task
        from repro.models.model import init_lm, lm_loss
        cfg = get_reduced("llama3.2-1b")
        fed = FedConfig(n_clients=1, s=1, local_steps=1, lr=0.02,
                        codec_up=codec, codec_down=down)
        params0, _ = init_lm(cfg, jax.random.PRNGKey(0))
        data, batch_fn = federated_token_task(0, 1, 32, 2, 16,
                                              cfg.vocab_size)
        alg = make_algorithm("spmd", fed, loss_fn=partial(lm_loss, cfg),
                             template=params0, batch_fn=batch_fn, cfg=cfg,
                             batch=2, seq=16, **kw)
        return alg, data, params0, jax.random.PRNGKey(1)
    from repro.data import make_federated_classification
    from repro.data.synthetic import client_batch
    from repro.models.mlp import init_mlp_classifier, mlp_loss
    d, hidden, classes = 16, 16, 4
    fed = FedConfig(n_clients=4, s=2, local_steps=1, lr=0.2, bits=8,
                    codec_up=codec, codec_down=down)
    part, _ = make_federated_classification(0, fed.n_clients, d=d,
                                            n_classes=classes)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(0), d, hidden,
                                     classes)
    alg = make_algorithm(alg_name, fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=lambda dd, k: client_batch(k, dd, d),
                         **kw)
    return alg, part, params0, jax.random.PRNGKey(1)


def _traceable(alg):
    """The (algorithm, init-state) pair the engine hooks trace. An
    algorithm with custom ``scan_rounds`` host control (adaptive bit-width)
    is analyzed through its current-bits inner algorithm."""
    inner_of = getattr(alg, "_alg", None)
    if callable(getattr(alg, "scan_rounds", None)) and callable(inner_of):
        return inner_of(int(alg.fed.bits))
    return alg


def _codec_pipe(codec):
    """An ``ExchangePipeline`` with the codec's own γ derivation (bits,
    block, safety) — the interval analyzers trace through it."""
    from repro.compression.pipeline import ExchangePipeline
    return ExchangePipeline(bits=int(codec.bits), block=codec.block,
                            backend="jnp", safety=float(codec.safety))


def flow_checks(closed, target, d: int, where: str) -> List:
    """The PR 10 dataflow analyzers over one traced round program:
    wire-truth audit + γ-overflow interval proofs + divergence escapes.
    ``target`` is the algorithm whose round ``closed`` traces — its OWN
    resolved codecs are the declarations to audit against (algorithms pick
    per-direction defaults, e.g. an identity downlink broadcast)."""
    from repro.analysis.divergence import check_divergence
    from repro.analysis.intervals import (check_encode_intervals,
                                          check_gamma_window)
    from repro.analysis.wire import check_wire_truth
    from repro.compression.codecs import resolve_codec

    fed = target.fed
    up = getattr(target, "codec_up", None)
    dn = getattr(target, "codec_down", None)
    up = up if up is not None else resolve_codec(
        None, fed, direction="up", default="lattice")
    dn = dn if dn is not None else resolve_codec(
        None, fed, direction="down", default="lattice")
    decl_up = (up.wire_declaration(d)
               if hasattr(up, "wire_declaration") else None)
    decl_dn = (dn.wire_declaration(d)
               if hasattr(dn, "wire_declaration") else None)
    viols = check_wire_truth(closed, where=where, decl_up=decl_up,
                             decl_down=decl_dn, codec_up=up, codec_down=dn,
                             d=d)
    viols += check_divergence(closed, where)
    from repro.compression.pipeline import LatticeWire
    for direction, codec in (("up", up), ("down", dn)):
        if getattr(codec, "family", "") != "lattice":
            continue
        pipe = _codec_pipe(codec)
        # a grouped codec runs one batched exchange with per-message
        # moduli; each member's wrap proof is the uniform-width proof at
        # ITS bit-width (the interval domain cannot couple the levels row
        # to the matching γ rows, so prove member-by-member)
        member_bits = sorted(set(getattr(codec, "bits_per_client",
                                         (int(codec.bits),))))
        for b in member_bits:
            # unpacked uniform wire: packing is a relayout of in-range
            # codes, and γ/wrap are functions of the bit-width alone
            wire = LatticeWire(bits=int(b), pack=1)
            tag = (f"{where}/{direction}" if len(member_bits) == 1
                   else f"{where}/{direction}@bits{b}")
            viols += check_encode_intervals(pipe, wire, d, (1 << int(b),),
                                            tag)
            viols += check_gamma_window(pipe, wire, d, tag)
    return viols


def analyze_cell(alg_name: str, codec: str, *, donation: bool = True,
                 chunk: int = 2) -> Dict:
    """All jaxpr-level checks for one (algorithm, codec) cell."""
    import jax
    from repro.analysis.donation import audit_engine_chunk, donation_report
    from repro.analysis.jaxpr import analyze_jaxpr
    from repro.analysis.opbudget import (measure_round_counters,
                                         rotation_budget)
    from repro.fed.engine import RoundEngine
    cell = f"{alg_name}x{codec}"
    alg, data, params0, key = _build_cell(alg_name, codec)
    target = _traceable(alg)
    state = target.init(params0)
    eng = RoundEngine(target)

    viols = []
    closed_r = eng.traced_round(state, data, key)
    vs, ops = analyze_jaxpr(closed_r, f"{cell}/round")
    viols += vs
    model_dim = sum(int(x.size)
                    for x in jax.tree_util.tree_leaves(params0))
    viols += flow_checks(closed_r, target, model_dim, f"{cell}/round")
    closed_c = eng.traced_chunk(state, data, key, chunk)
    vs, ops_chunk = analyze_jaxpr(closed_c, f"{cell}/chunk{chunk}")
    viols += vs

    report: Dict = {"ops_round": ops, "ops_chunk": ops_chunk}
    # measure ONCE: a second trace of the same (self, avals) signature hits
    # the pjit trace cache and the python body (where the counters live)
    # never re-runs
    measured = measure_round_counters(target, state, data, key)
    if measured is not None:
        report["rotation_counters"] = dict(measured.counters)
        # the s+1/s+1 budget binds algorithms that route through the fused
        # rotated exchange; an inherited-but-unused pipeline (scaffold runs
        # stateless codec encodes instead) legitimately counts zero
        if any(measured.counters.values()):
            viols += measured.expect(f"{cell}/round",
                                     rotation_budget(int(target.fed.s)))
    if donation:
        viols += audit_engine_chunk(eng, state, data, key, chunk,
                                    f"{cell}/chunk{chunk}")
        report["donation"] = donation_report(eng, state, data, key, chunk)
    report["violations"] = [v.as_dict() for v in viols]
    return report


def _trace_exchange(codec_up_spec: str, codec_dn_spec: str,
                    transport_name: str, d: int, n: int,
                    model_sharded: bool = True):
    """Trace the shard-local exchange for one codec/transport pair on an
    abstract (n, 2) data×model mesh; returns (closed, up, dn, transport).

    ``model_sharded`` mirrors the pod layout the launcher builds (leaves
    sharded over the model axes — the exchange folds the model-rank into
    the rotation key, so each rank must own its block). The historical
    ``rs_transport_audit`` traces the replicated layout instead (its byte
    pins are at the full leaf dimension)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.compression.codecs import resolve_codec
    from repro.compression.transports import make_transport
    from repro.configs.base import FedConfig
    from repro.core.exchange_local import make_shardlocal_exchange

    mesh = AbstractMesh((n, 2), ("data", "model"))
    fed = FedConfig(n_clients=n, s=n, bits=8, codec_up=codec_up_spec,
                    codec_down=codec_dn_spec)
    up = resolve_codec(None, fed, direction="up", default="lattice")
    dn = resolve_codec(None, fed, direction="down", default="lattice")
    transport = make_transport(transport_name)
    srv_ps = {"w": P("model")} if model_sharded else {"w": P()}
    cl_ps = {"w": P("data", "model")} if model_sharded else {"w": P("data")}
    ex = make_shardlocal_exchange(
        up, dn, mesh, srv_ps, cl_ps, "data", n, transport=transport)
    srv = {"w": jax.ShapeDtypeStruct((d,), jnp.float32)}
    cl = {"w": jax.ShapeDtypeStruct((n, d), jnp.float32)}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    closed = jax.make_jaxpr(ex)(srv, cl, cl, key)
    return closed, up, dn, transport


def analyze_exchange_cell(codec: str, transport_name: str,
                          d: int = 1 << 16, n: int = 4) -> Dict:
    """Wire-truth + byte-budget + divergence (+ γ_rs wrap proof) for one
    codec × transport pair of the shard-local exchange."""
    from repro.analysis.divergence import check_divergence
    from repro.analysis.intervals import check_rs_gamma_window
    from repro.analysis.jaxpr import op_report
    from repro.analysis.wire import check_wire_truth

    cell = _exchange_cell_name(codec, transport_name)
    dn_spec = codec if codec.split(":")[0] in _DOWNLINK_OK else ""
    closed, up, dn, transport = _trace_exchange(codec, dn_spec,
                                                transport_name, d, n)
    budget = transport.wire_budget(up, dn, d, n)
    d_leaf = d + (-d) % 1024   # the exchange pads leaves to 1024 multiples
    decl_up = (up.wire_declaration(d_leaf)
               if hasattr(up, "wire_declaration") else None)
    decl_dn = (dn.wire_declaration(d_leaf)
               if hasattr(dn, "wire_declaration") else None)
    viols = check_wire_truth(closed, where=cell, decl_up=decl_up,
                             decl_down=decl_dn, codec_up=up, codec_down=dn,
                             d=d_leaf, budget=budget)
    viols += check_divergence(closed, cell)
    if (transport_name == "reduce_scatter"
            and getattr(dn, "family", "") == "lattice"):
        viols += check_rs_gamma_window(_codec_pipe(dn), dn.wire(), d_leaf,
                                       n, cell)
    return {"ops": op_report(closed),
            "violations": [v.as_dict() for v in viols]}


def sentinel_run(alg_name: str, *, rounds: int = 4, chunk: int = 2,
                 codec: str = "lattice") -> Dict:
    """Prove one-compile-per-(algorithm, chunk length) on a real scanned
    ``simulate()`` run: record the chunk fingerprint before the run, run,
    re-record, then interrogate every engine jit cache."""
    import jax
    from repro.analysis.sentinel import RecompileSentinel
    from repro.fed.simulate import simulate
    alg, data, params0, key = _build_cell(alg_name, codec)
    target = _traceable(alg)
    sentinel = RecompileSentinel()
    tag = f"{alg_name}x{codec}"

    from repro.fed.engine import RoundEngine
    pre = RoundEngine(target).traced_chunk(target.init(params0), data,
                                           jax.random.PRNGKey(1), chunk)
    sentinel.record((tag, chunk), pre)
    simulate(alg, params0, data, jax.random.PRNGKey(2), rounds=rounds,
             eval_every=0, scan_chunk=chunk)
    engines = [("", e) for e in [getattr(alg, "_round_engine", None)]
               if e is not None]
    # an adaptive wrapper compiles one program per visited bit-width: same
    # one-compile contract, separate tag per width (the width the pre-run
    # fingerprint pinned keeps the bare tag)
    engines += [("" if b == int(alg.fed.bits) else f"@bits{b}", e)
                for b, e in getattr(alg, "_engines", {}).items()]
    compiles = {}
    for subtag, eng in engines:
        sentinel.check_engine((tag + subtag, chunk), eng)
        if not callable(getattr(eng.alg, "device_round", None)):
            # engine over a custom-scan_rounds wrapper (adaptive): its
            # chunk cache is never populated — the inner engines above
            # carry the compiled programs — and it has nothing to trace
            continue
        post = eng.traced_chunk(eng.alg.init(params0), data,
                                jax.random.PRNGKey(1), chunk)
        sentinel.record((tag + subtag, chunk), post)
        for length, fn in eng._chunk_fns.items():
            try:
                compiles[f"chunk{length}{subtag}"] = fn._cache_size()
            except AttributeError:
                pass
    return {"violations": [v.as_dict() for v in sentinel.report()],
            "compiles": compiles}


def rs_transport_audit(d: int = 1 << 16, n: int = 4) -> Dict:
    """Trace the fused ``shard_local_rs`` exchange on an ABSTRACT (4, 2)
    data×model mesh (no devices needed — ``AbstractMesh`` + ``make_jaxpr``
    trace the same shard_map program a pod runs) and budget its per-device
    collective payload against the transport's own
    :meth:`~repro.compression.transports.ReduceScatterSum.wire_budget`
    declaration (PR 9 pinned these caps by hand; the declaration now IS
    the budget):

      * the redistribution ``all_gather`` must move integer codes plus
        scalar f32 γ rows only — a regression back to the fp32 re-gather
        (``all_gather_fbytes`` jumping from a handful of scalars to d·4)
        fails the gate,
      * no full-size fp32 ``psum`` may sneak back in either (the
        exact-psum fallback silently replacing the coded path on a
        shardable chunk would show up as ``psum_fbytes`` ≈ d·4).

    The reducing phase (``psum_scatter`` of the snapped fp32 chunks) is
    the one collective that legitimately moves d·4 float bytes.
    """
    from repro.analysis.jaxpr import analyze_jaxpr
    from repro.analysis.opbudget import check_collective_bytes

    closed, up, dn, transport = _trace_exchange(
        "lattice_packed:bits=4", "lattice_packed:bits=4", "reduce_scatter",
        d, n, model_sharded=False)
    where = "shard_local_rs/exchange@mesh(4,2)"
    viols, ops = analyze_jaxpr(closed, where)
    viols += check_collective_bytes(closed, where,
                                    transport.wire_budget(up, dn, d, n).caps)
    return {"ops": ops, "violations": [v.as_dict() for v in viols]}


def run_lint(*, quick: bool = False, only: Optional[str] = None,
             donation: Optional[bool] = None,
             sentinel: Optional[bool] = None, verbose: bool = True,
             timings: Optional[Dict[str, float]] = None) -> Dict:
    """Full gate: AST rules + the jaxpr matrix + the exchange matrix
    (+ donation/sentinel unless ``quick``). Returns the ANALYSIS.json
    payload — deterministic by construction: wall-clock seconds go to the
    optional ``timings`` dict (cell name → seconds), never the report.

    An ``only`` selector that matches no cell raises ``SystemExit`` with
    the full cell list — a typo must not silently run an empty gate."""
    donation = (not quick) if donation is None else donation
    sentinel = (not quick) if sentinel is None else sentinel
    timings = {} if timings is None else timings
    t0 = time.time()
    if only is not None and not any(only in name for name in list_cells()):
        raise SystemExit(
            f"--only {only!r} matches no analysis cell; known cells:\n  "
            + "\n  ".join(list_cells()))
    src_root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))   # .../src/repro
    from repro.analysis.astlint import lint_path
    ast_viols = lint_path(src_root)
    n_viols = len(ast_viols)

    def _run(section: Dict, name: str, label: str, fn) -> None:
        nonlocal n_viols
        tc = time.time()
        try:
            rep = fn()
        except Exception as e:   # an unanalyzable cell is itself a finding
            rep = {"violations": [{
                "rule": "analyzer-error", "where": name,
                "detail": f"{type(e).__name__}: {e}"}]}
        timings[label] = round(time.time() - tc, 2)
        section[name] = rep
        n_viols += len(rep["violations"])
        if verbose:
            status = ("ok" if not rep["violations"]
                      else f"{len(rep['violations'])} VIOLATIONS")
            print(f"# {label}: {status} ({timings[label]}s)", flush=True)

    matrix: Dict[str, Dict] = {}
    for alg_name, codec in _cells(only):
        cell = f"{alg_name}x{codec}"
        _run(matrix, cell, cell,
             lambda a=alg_name, c=codec: analyze_cell(a, c,
                                                      donation=donation))
    exchange: Dict[str, Dict] = {}
    for codec, transport in _exchange_cells(only):
        cell = _exchange_cell_name(codec, transport)
        _run(exchange, cell, cell,
             lambda c=codec, t=transport: analyze_exchange_cell(c, t))
    rs_section: Dict[str, Dict] = {}
    if only is None or only in "rs_transport":
        _run(rs_section, "rs_transport", "rs_transport", rs_transport_audit)
    sentinels: Dict[str, Dict] = {}
    if sentinel:
        for alg_name, codec in _cells(only):
            if codec != "lattice":   # one scanned run per algorithm
                continue
            _run(sentinels, alg_name, f"sentinel:{alg_name}",
                 lambda a=alg_name: sentinel_run(a))
    timings["total"] = round(time.time() - t0, 2)
    return {
        "schema": "analysis.v2",
        "quick": bool(quick),
        "violations_total": n_viols,
        "ast": {"root": src_root,
                "violations": [v.as_dict() for v in ast_viols]},
        "matrix": matrix,
        "exchange": exchange,
        "rs_transport": rs_section.get("rs_transport", {}),
        "sentinel": sentinels,
    }


def default_json_path() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))   # repo root
    return os.path.join(root, "ANALYSIS.json")


def _arg_value(argv: List[str], flag: str) -> Optional[str]:
    if flag in argv:
        i = argv.index(flag)
        if i + 1 < len(argv):
            return argv[i + 1]
    return None


def _write_timings(timings: Dict[str, float]) -> str:
    """Raw wall-clock per cell — gitignored ``bench_out/``, never the
    committed ANALYSIS.json (which must be byte-stable across runs)."""
    root = os.path.dirname(default_json_path())
    out_dir = os.path.join(root, "bench_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "analysis_timings.json")
    with open(path, "w") as f:
        json.dump(timings, f, indent=2, sort_keys=True)
    return path


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--list" in argv:
        for name in list_cells():
            print(name)
        return 0
    timings: Dict[str, float] = {}
    report = run_lint(quick="--quick" in argv,
                      only=_arg_value(argv, "--only"), timings=timings)
    path = _arg_value(argv, "--json") or default_json_path()
    if path != "-":
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"# wrote {path}")
    print(f"# timings: {_write_timings(timings)}")
    n = report["violations_total"]
    print(f"# repro.analysis.lint: {n} violation(s) in "
          f"{timings.get('total', 0.0)}s")
    if n:
        for v in report["ast"]["violations"]:
            print(f"AST  {v['rule']} {v['where']}: {v['detail']}")
        for cell, rep in (list(report["matrix"].items())
                          + list(report["exchange"].items())
                          + [("rs_transport", report["rs_transport"])]
                          + list(report["sentinel"].items())):
            for v in rep.get("violations", []):
                print(f"JXPR {v['rule']} {v['where']}: {v['detail']}")
    return min(n, 125)


if __name__ == "__main__":
    sys.exit(main())
