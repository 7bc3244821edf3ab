"""Jaxpr-level invariant analyzers for traced federated rounds.

The repo's correctness story rests on properties of the TRACED program, not
the python that builds it: a round is one device-resident computation (no
host callbacks mid-scan), arithmetic stays in the f32 regime the bit
accounting assumes, and PRNG keys are consumed once per derivation path so
client schedules survive resharding. This module walks closed jaxprs (from
:meth:`repro.fed.engine.RoundEngine.traced_round` / ``traced_chunk``) and
checks each of those invariants mechanically.

Every checker returns a list of :class:`Violation` — empty means clean.
:func:`analyze_jaxpr` bundles all jaxpr checks plus an op-count report
(consumed by :mod:`repro.analysis.opbudget`).

**Key-discipline policy.** The lattice exchange *intentionally* consumes one
key twice with the SAME derivation — shared-randomness dithers: the decoder
re-splits the encoder's key to reproduce its rotation/dither draws (see
``LatticeCodec.decode``). Statically, identical (primitive, params,
output-aval) consumption signatures are therefore the shared-randomness
idiom, not a bug. What corrupts schedules is a key consumed by two
*distinct* derivations — e.g. ``uniform(k, (8,))`` and ``normal(k, (4,))``
— which silently correlates two streams. So the rule is: flag a key var
only when its consumption signatures (over ``random_bits``/``random_split``)
are distinct; ``random_fold_in`` never flags (folding is domain separation —
the canonical FIX for reuse).
"""
from __future__ import annotations

import dataclasses
import math
from collections import Counter, defaultdict
from typing import Any, Dict, Iterator, List, Tuple

from jax import dtypes
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal


@dataclasses.dataclass(frozen=True)
class Violation:
    """One analyzer finding: ``rule`` id, ``where`` it was found (e.g.
    ``"quafl×lattice/traced_round"``), human-readable ``detail``."""
    rule: str
    where: str
    detail: str

    def as_dict(self) -> Dict[str, str]:
        return {"rule": self.rule, "where": self.where,
                "detail": self.detail}


# ---------------------------------------------------------------------------
# generic recursion over sub-jaxprs
# ---------------------------------------------------------------------------

def _jaxprs_in(v) -> Iterator[Jaxpr]:
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _jaxprs_in(x)


def subjaxprs(eqn) -> Iterator[Jaxpr]:
    """All jaxprs nested in an equation's params (pjit ``jaxpr``, scan
    ``jaxpr``, cond ``branches``, while ``cond_jaxpr``/``body_jaxpr``,
    shard_map ``jaxpr``, custom_* ``call_jaxpr``/``jvp_jaxpr_fun`` ...)."""
    for v in eqn.params.values():
        yield from _jaxprs_in(v)


def iter_eqns(jaxpr: Jaxpr) -> Iterator[Any]:
    """Depth-first iterator over every equation, descending into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for j in subjaxprs(eqn):
            yield from iter_eqns(j)


def _as_jaxpr(j) -> Jaxpr:
    return j.jaxpr if isinstance(j, ClosedJaxpr) else j


# ---------------------------------------------------------------------------
# host callbacks / debug prints in the hot path
# ---------------------------------------------------------------------------

CALLBACK_PRIMS = frozenset({
    "debug_callback", "pure_callback", "io_callback", "callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
    "debug_print",
})


def check_host_callbacks(closed, where: str) -> List[Violation]:
    """No host round-trips inside a traced round: ``jax.debug.print``,
    ``pure_callback`` etc. serialize the device stream and break the
    one-sync-per-chunk contract of the scanned engine."""
    out = []
    for eqn in iter_eqns(_as_jaxpr(closed)):
        if eqn.primitive.name in CALLBACK_PRIMS:
            out.append(Violation(
                "host-callback", where,
                f"host callback primitive {eqn.primitive.name!r} in traced "
                f"round body"))
    return out


# ---------------------------------------------------------------------------
# implicit f64 / wide-dtype promotion
# ---------------------------------------------------------------------------

WIDE_DTYPES = ("float64", "complex128")


def check_wide_dtypes(closed, where: str) -> List[Violation]:
    """No f64/c128 values anywhere in the trace — the wire accounting and
    the Pallas kernels assume the f32 regime; a weak-type promotion to f64
    silently doubles buffer sizes and invalidates ``bits_*`` metrics."""
    out = []
    seen = set()
    for eqn in iter_eqns(_as_jaxpr(closed)):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in WIDE_DTYPES and dt not in seen:
                seen.add(dt)
                out.append(Violation(
                    "wide-dtype", where,
                    f"{dt} value produced by {eqn.primitive.name!r} "
                    f"({aval}) — implicit 64-bit promotion in traced round"))
    return out


# ---------------------------------------------------------------------------
# PRNG-key discipline
# ---------------------------------------------------------------------------

_DRAW = frozenset({"random_bits"})
_SPLIT = frozenset({"random_split"})
_FOLD = frozenset({"random_fold_in"})
_ALIAS = frozenset({"random_wrap", "random_unwrap"})
_CONSUMERS = _DRAW | _SPLIT

# jax.random's composite rejection samplers consume one key several ways
# internally (knuth vs rejection branches, both materialized under vmap via
# select_n) — BY DESIGN, per-lane exclusive. From the caller's perspective
# each is ONE draw: treat the jitted helper as an atomic consumer and do
# not descend.
_ATOMIC_SAMPLERS = frozenset({
    "_poisson", "_poisson_knuth", "_poisson_rejection",
    "_gamma", "_gamma_impl", "_gamma_one", "_gamma_grad",
    "_binomial", "_binomial_inversion", "_binomial_btrs",
})


def _consume_sig(eqn) -> str:
    """Signature of a key consumption: primitive + params + output avals.
    Two consumptions with the SAME signature produce identical streams —
    that's the shared-randomness idiom; DISTINCT signatures on one key are
    two correlated-but-different streams, i.e. the bug."""
    params = sorted((k, repr(v)) for k, v in eqn.params.items())
    outs = ",".join(str(getattr(v, "aval", "?")) for v in eqn.outvars)
    return f"{eqn.primitive.name}{params!r}->{outs}"


def _is_key_var(var) -> bool:
    aval = getattr(var, "aval", None)
    try:
        return aval is not None and dtypes.issubdtype(aval.dtype,
                                                      dtypes.prng_key)
    except (TypeError, AttributeError):
        return False


def _key_usage(jaxpr: Jaxpr, memo) -> Tuple[List[Tuple[str, List[str]]],
                                            Dict[int, Counter]]:
    """Per-jaxpr key-consumption analysis.

    Returns ``(violations, invar_sigs)`` where ``violations`` are
    ``(varname, [distinct sigs])`` pairs and ``invar_sigs`` maps an invar
    POSITION to the Counter of consumption signatures that flow from it —
    so a caller can propagate a sub-jaxpr's consumption onto the operands
    it passed in (this is what catches reuse across a ``scan``/``cond``
    boundary).
    """
    if id(jaxpr) in memo:
        return memo[id(jaxpr)]
    rep: Dict[Any, Any] = {}   # wrap/unwrap alias chains -> representative
    # a raw uint32 seed wrapped via random_wrap IS a key for discipline
    # purposes — remember representatives whose alias chain touches a key
    keyish: set = set()

    def find(v):
        while v in rep:
            v = rep[v]
        return v

    use: Dict[Any, Counter] = defaultdict(Counter)
    viols: List[Tuple[str, List[str]]] = []

    def charge(var, sig, count=1):
        if not isinstance(var, Literal):
            use[find(var)][sig] += count

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _ALIAS:
            src = eqn.invars[0]
            if not isinstance(src, Literal):
                r = find(src)
                rep[eqn.outvars[0]] = r
                if _is_key_var(eqn.outvars[0]) or _is_key_var(src):
                    keyish.add(r)
            continue
        if name in _CONSUMERS:
            charge(eqn.invars[0], _consume_sig(eqn))
            continue
        if name in _FOLD:
            # fold_in is domain separation: never a violation, and the
            # folded OUTPUT is a fresh derivation path.
            continue
        if (name == "jit"
                and str(eqn.params.get("name", "")) in _ATOMIC_SAMPLERS):
            outs = ",".join(str(getattr(v, "aval", "?"))
                            for v in eqn.outvars)
            sig = f"sampler:{eqn.params['name']}->{outs}"
            for v in eqn.invars:
                if not isinstance(v, Literal) and _is_key_var(v):
                    charge(v, sig)
            continue
        subs = list(subjaxprs(eqn))
        if not subs:
            continue
        if eqn.primitive.name == "cond":
            # branches are ALTERNATIVES: exactly one executes, so the same
            # key consumed differently by different branches is NOT reuse
            # (jax.random.poisson does exactly this internally). Collapse
            # each operand's cross-branch consumption into one synthetic
            # signature — outer consumption of the same key still collides
            # with it, and within-branch reuse is judged inside the branch.
            ops = list(eqn.invars)[1:]
            branch_sigs: Dict[int, set] = defaultdict(set)
            for sub in subs:
                sviols, sigs = _key_usage(sub, memo)
                viols.extend(sviols)
                for pos, cnt in sigs.items():
                    branch_sigs[pos].update(cnt)
            for pos, sigset in branch_sigs.items():
                if pos < len(ops):
                    charge(ops[pos], f"cond({'|'.join(sorted(sigset))})")
            continue
        # map each sub-jaxpr invar position onto the eqn operand feeding it
        for sub, operands in _operand_maps(eqn, subs):
            sviols, sigs = _key_usage(sub, memo)
            viols.extend(sviols)
            for pos, cnt in sigs.items():
                if pos < len(operands) and operands[pos] is not None:
                    for sig, c in cnt.items():
                        charge(operands[pos], sig, c)

    for var, cnt in use.items():
        distinct = sorted(cnt)
        if len(distinct) >= 2 and (_is_key_var(var) or var in keyish):
            viols.append((str(var), [s[:120] for s in distinct]))

    invar_sigs: Dict[int, Counter] = {}
    for i, v in enumerate(jaxpr.invars):
        r = find(v)
        acc = Counter()
        for var, cnt in use.items():
            if var is r:
                acc.update(cnt)
        if acc:
            invar_sigs[i] = acc
    memo[id(jaxpr)] = (viols, invar_sigs)
    return viols, invar_sigs


def _operand_maps(eqn, subs):
    """Yield ``(sub_jaxpr, operands)`` where ``operands[i]`` is the eqn
    invar feeding sub-jaxpr invar ``i`` (None where unmapped). Handles the
    control-flow primitives whose operand layout is not positional."""
    name = eqn.primitive.name
    inv = list(eqn.invars)
    # (cond is handled by the caller — its branches are alternatives)
    if name == "while":
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        carry = inv[cn + bn:]
        cond_j, body_j = subs[0], subs[1] if len(subs) > 1 else subs[0]
        yield cond_j, inv[:cn] + carry
        yield body_j, inv[cn:cn + bn] + carry
        return
    # scan: invars = consts + init + xs and body invars = consts + carry + x
    # line up positionally (xs map to their stacked operand, which is the
    # right identity for reuse tracking). pjit/closed_call/shard_map are
    # positional too. Anything whose arity does not line up (custom_jvp /
    # custom_vjp carry extra tangent/residual jaxprs) is NOT mapped — the
    # sub-jaxpr is still analyzed internally, but its consumption is not
    # charged to outer operands (conservative: may miss cross-boundary
    # reuse there, never false-positives).
    for sub in subs:
        if len(sub.invars) == len(inv):
            yield sub, inv
        else:
            yield sub, []


def check_key_discipline(closed, where: str) -> List[Violation]:
    """Flag any PRNG key var consumed by two DISTINCT random derivations.

    Identical consumption signatures (same primitive, params, and output
    avals) are permitted — the lattice shared-dither idiom re-derives the
    encoder's randomness by design. ``fold_in`` never flags.
    """
    viols, _ = _key_usage(_as_jaxpr(closed), {})
    # a shared sub-jaxpr (jit-cached helper) can be reached through several
    # parents; report each distinct finding once
    seen = set()
    out = []
    for var, sigs in viols:
        k = (var, tuple(sigs))
        if k in seen:
            continue
        seen.add(k)
        out.append(Violation(
            "key-reuse", where,
            f"key {var} consumed by {len(sigs)} distinct random "
            f"derivations: {sigs}"))
    return out


# ---------------------------------------------------------------------------
# op-count report (consumed by the op-budget audit)
# ---------------------------------------------------------------------------

# primitives whose counts the budget/watchdog report tracks explicitly
TRACKED_OPS = ("convert_element_type", "device_put",
               "psum_scatter", "reduce_scatter", "all_gather", "all_reduce",
               "ppermute", "psum")


def op_counts(closed) -> Counter:
    """Counter of every primitive in the (recursively walked) jaxpr."""
    return Counter(e.primitive.name for e in iter_eqns(_as_jaxpr(closed)))


# collectives whose per-device payload the report estimates: gathers charge
# their OUTPUT avals (bytes every device receives), reductions their INPUT
# avals (bytes every device contributes)
_GATHER_OPS = frozenset({"all_gather"})
_REDUCE_OPS = frozenset({"psum", "psum_scatter", "reduce_scatter",
                         "all_reduce", "ppermute"})


def collective_bytes(closed) -> Dict[str, int]:
    """Per-device moved-bytes estimate for every collective in the trace,
    split by element kind: ``<prim>_fbytes`` (float payload) vs
    ``<prim>_ibytes`` (integer codes). This is the quantity a regression
    from the coded redistribution back to an fp32 re-gather inflates by
    ~d·4 — counts alone cannot see it (same number of ``all_gather`` eqns,
    radically different wire)."""
    out: Dict[str, int] = {}
    for eqn in iter_eqns(_as_jaxpr(closed)):
        name = eqn.primitive.name
        if name in _GATHER_OPS:
            vs = eqn.outvars
        elif name in _REDUCE_OPS:
            vs = eqn.invars
        else:
            continue
        for v in vs:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None or not hasattr(dt, "itemsize"):
                continue
            kind = "f" if getattr(dt, "kind", "") == "f" else "i"
            key = f"{name}_{kind}bytes"
            out[key] = out.get(key, 0) + (int(math.prod(aval.shape))
                                          * int(dt.itemsize))
    return out


def op_report(closed) -> Dict[str, int]:
    """The tracked subset of :func:`op_counts` plus the per-collective
    moved-bytes estimate and total eqn count — transfer/convert and
    collective traffic that make e.g. the known fp32 re-gather after
    ``psum_scatter`` visible as a counted AND sized quantity."""
    c = op_counts(closed)
    rep = {k: c[k] for k in TRACKED_OPS if c[k]}
    rep.update(collective_bytes(closed))
    rep["eqns_total"] = sum(c.values())
    return rep


def analyze_jaxpr(closed, where: str) -> Tuple[List[Violation],
                                               Dict[str, int]]:
    """All jaxpr invariant checks on one closed jaxpr + its op report."""
    viols = (check_host_callbacks(closed, where)
             + check_wide_dtypes(closed, where)
             + check_key_discipline(closed, where))
    return viols, op_report(closed)
