"""Generic forward dataflow engine over closed jaxprs.

PR 8/9 analyzers *count* things (ops, bytes, key uses) by walking eqns;
this module *derives facts along dataflow edges*: a configurable abstract
domain (lattice values + join + per-primitive transfer functions) is
propagated forward through a closed jaxpr by a worklist/fixpoint
interpreter that understands the control primitives jax actually emits:

  - ``pjit`` / call-like primitives: recurse into the subjaxpr (with an
    optional precise *call override* so a domain can summarise a known
    callee, e.g. ``jnp.mod``'s ``remainder`` wrapper, more tightly than
    its body).
  - ``scan``: iterate the body to a fixpoint on the carry values (join
    per iteration, widening to top after ``max_fixpoint_iters``), then a
    final observed pass so analyzer hooks see post-fixpoint facts once.
  - ``while``: same carry fixpoint through the body; the cond jaxpr is
    analyzed for its observations only.
  - ``cond``: analyze every branch with the same operand facts and join
    the branch outputs (branches are alternatives, not sequences).
  - ``shard_map``: delegate entry/exit value mapping to the domain so a
    mesh-aware analysis (e.g. divergence) can seed per-axis facts from
    ``in_specs`` and audit escapes against ``out_specs``.

Domains subclass :class:`FlowDomain`; analyzers live in ``wire.py``,
``intervals.py`` and ``divergence.py``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

from jax._src.core import DropVar  # not exported by jax.extend.core
from jax.extend import core as jcore

# Primitives whose params hold a single positionally-compatible subjaxpr.
_CALL_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")

# Fixpoint iteration budget before widening a carry to top. Carries in
# this repo's round programs stabilise in 2-3 joins; the cap only guards
# against domains with infinite ascending chains (e.g. intervals).
MAX_FIXPOINT_ITERS = 16


class FlowDomain:
    """Abstract domain: lattice values, join, and transfer functions.

    The engine never inspects values; it only moves them around and asks
    the domain to combine them. Subclasses must implement ``top``,
    ``join`` and ``transfer``; everything else has sound defaults.
    """

    def top(self, aval) -> Any:
        raise NotImplementedError

    def join(self, a: Any, b: Any) -> Any:
        raise NotImplementedError

    def transfer(self, eqn, ins: list[Any]) -> list[Any]:
        """Abstract outputs of a non-control equation."""
        raise NotImplementedError

    def literal(self, lit) -> Any:
        """Value for a jaxpr literal operand."""
        return self.top(lit.aval)

    def const(self, aval, val) -> Any:
        """Value for a closed-jaxpr constant."""
        return self.top(aval)

    def veq(self, a: Any, b: Any) -> bool:
        """Equality used for fixpoint convergence checks."""
        return a == b

    def call_override(self, eqn, closed_sub, ins: list[Any]) -> list[Any] | None:
        """Optional precise summary for a call-like eqn; None recurses."""
        return None

    def enter_shard_map(self, eqn, ins: list[Any]) -> list[Any]:
        """Map outer operand values to body invar values."""
        return ins

    def exit_shard_map(self, eqn, outs: list[Any], ctx: FlowContext) -> list[Any]:
        """Map body output values to outer eqn output values."""
        return outs

    def on_eqn(self, eqn, ins: list[Any], outs: list[Any], ctx: FlowContext) -> None:
        """Observation hook; called exactly once per eqn per analysis."""


@dataclass
class FlowContext:
    """Mutable per-analysis state handed to domain hooks."""

    path: tuple[str, ...] = ()
    observe: bool = True
    # Scratch space for domains (e.g. collected facts/violations).
    facts: list = field(default_factory=list)

    def at(self, label: str, observe: bool | None = None) -> FlowContext:
        sub = FlowContext(
            path=self.path + (label,),
            observe=self.observe if observe is None else observe,
            facts=self.facts,
        )
        return sub

    @property
    def where(self) -> str:
        return "/".join(self.path) or "<root>"


@dataclass
class FlowResult:
    out_vals: list[Any]
    context: FlowContext


def _read(domain: FlowDomain, env: dict, atom) -> Any:
    if isinstance(atom, jcore.Literal):
        return domain.literal(atom)
    try:
        return env[atom]
    except KeyError:  # defensive: unbound var (shouldn't happen)
        return domain.top(atom.aval)


def _write(env: dict, var, val) -> None:
    if isinstance(var, DropVar):
        return
    env[var] = val


def _tops(domain: FlowDomain, eqn) -> list[Any]:
    return [domain.top(v.aval) for v in eqn.outvars]


def _closed(sub) -> jcore.ClosedJaxpr:
    if isinstance(sub, jcore.ClosedJaxpr):
        return sub
    return jcore.ClosedJaxpr(sub, ())


def analyze_flow(closed, domain: FlowDomain, inputs: list[Any] | None = None,
                 ctx: FlowContext | None = None) -> FlowResult:
    """Run ``domain`` forward over ``closed`` and return abstract outputs.

    ``inputs`` seeds the top-level invars (defaults to ``domain.top``).
    The returned context carries whatever facts the domain collected via
    ``ctx.facts`` in its ``on_eqn`` hook.
    """
    closed = _closed(closed)
    jaxpr = closed.jaxpr
    if inputs is None:
        inputs = [domain.top(v.aval) for v in jaxpr.invars]
    if len(inputs) != len(jaxpr.invars):
        raise ValueError(
            f"analyze_flow: {len(inputs)} seeds for {len(jaxpr.invars)} invars")
    ctx = ctx or FlowContext()
    env: dict = {}
    for v, val in zip(jaxpr.invars, inputs):
        _write(env, v, val)
    for cv, c in zip(jaxpr.constvars, closed.consts):
        _write(env, cv, domain.const(cv.aval, c))
    _run_block(jaxpr, env, domain, ctx)
    outs = [_read(domain, env, v) for v in jaxpr.outvars]
    return FlowResult(out_vals=outs, context=ctx)


def _run_block(jaxpr, env: dict, domain: FlowDomain, ctx: FlowContext) -> None:
    for idx, eqn in enumerate(jaxpr.eqns):
        ins = [_read(domain, env, a) for a in eqn.invars]
        outs = _eqn_outputs(eqn, ins, domain, ctx, idx)
        for v, val in zip(eqn.outvars, outs):
            _write(env, v, val)
        if ctx.observe:
            domain.on_eqn(eqn, ins, outs, ctx)


def _run_sub(sub, ins: list[Any], domain: FlowDomain, ctx: FlowContext) -> list[Any]:
    """Analyze a subjaxpr with the given invar seeds; return outvar values."""
    sub = _closed(sub)
    res = analyze_flow(sub, domain, inputs=ins, ctx=ctx)
    return res.out_vals


def _eqn_outputs(eqn, ins: list[Any], domain: FlowDomain, ctx: FlowContext,
                 idx: int) -> list[Any]:
    name = eqn.primitive.name
    if name == "scan":
        return _scan(eqn, ins, domain, ctx.at(f"scan@{idx}"))
    if name == "while":
        return _while(eqn, ins, domain, ctx.at(f"while@{idx}"))
    if name == "cond":
        return _cond(eqn, ins, domain, ctx.at(f"cond@{idx}"))
    if name == "shard_map":
        return _shard_map(eqn, ins, domain, ctx.at(f"shard_map@{idx}"))
    sub = _find_call_jaxpr(eqn)
    if sub is not None:
        closed_sub = _closed(sub)
        override = domain.call_override(eqn, closed_sub, ins)
        if override is not None:
            return override
        if len(closed_sub.jaxpr.invars) == len(ins):
            label = eqn.params.get("name", name)
            return _run_sub(closed_sub, ins, domain, ctx.at(f"{name}:{label}@{idx}"))
        return _tops(domain, eqn)  # call with odd arity: stay sound
    return domain.transfer(eqn, ins)


def _find_call_jaxpr(eqn):
    for key in _CALL_JAXPR_KEYS:
        sub = eqn.params.get(key)
        if isinstance(sub, (jcore.Jaxpr, jcore.ClosedJaxpr)):
            return sub
    return None


def _fixpoint_carry(body, consts: list[Any], carry: list[Any], extras: list[Any],
                    num_carry: int, domain: FlowDomain, ctx: FlowContext):
    """Iterate ``body`` joining the carry until stable (or widen to top).

    Returns (final_carry, final_body_outs) where final_body_outs is from
    one *observed* pass run with the post-fixpoint carry.
    """
    body = _closed(body)
    for _ in range(MAX_FIXPOINT_ITERS):
        outs = _run_sub(body, consts + carry + extras, domain,
                        ctx.at("fix", observe=False))
        new_carry = [domain.join(c, o) for c, o in zip(carry, outs[:num_carry])]
        if all(domain.veq(c, n) for c, n in zip(carry, new_carry)):
            break
        carry = new_carry
    else:
        carry = [domain.top(v.aval)
                 for v in body.jaxpr.invars[len(consts):len(consts) + num_carry]]
    outs = _run_sub(body, consts + carry + extras, domain, ctx.at("body"))
    carry = [domain.join(c, o) for c, o in zip(carry, outs[:num_carry])]
    return carry, outs


def _scan(eqn, ins: list[Any], domain: FlowDomain, ctx: FlowContext) -> list[Any]:
    n_const = eqn.params["num_consts"]
    n_carry = eqn.params["num_carry"]
    consts = ins[:n_const]
    init = ins[n_const:n_const + n_carry]
    # Per-iteration slices of the stacked xs share the stacked abstract
    # value (facts here are shape-independent).
    xs = ins[n_const + n_carry:]
    carry, outs = _fixpoint_carry(eqn.params["jaxpr"], consts, init, xs,
                                  n_carry, domain, ctx)
    ys = outs[n_carry:]
    return list(carry) + list(ys)


def _while(eqn, ins: list[Any], domain: FlowDomain, ctx: FlowContext) -> list[Any]:
    n_cc = eqn.params["cond_nconsts"]
    n_bc = eqn.params["body_nconsts"]
    cond_consts = ins[:n_cc]
    body_consts = ins[n_cc:n_cc + n_bc]
    init = ins[n_cc + n_bc:]
    carry, _ = _fixpoint_carry(eqn.params["body_jaxpr"], body_consts, init, [],
                               len(init), domain, ctx)
    # The loop may run zero times: join the fixpoint with the init values.
    carry = [domain.join(c, i) for c, i in zip(carry, init)]
    _run_sub(eqn.params["cond_jaxpr"], cond_consts + carry, domain, ctx.at("cond"))
    return carry


def _cond(eqn, ins: list[Any], domain: FlowDomain, ctx: FlowContext) -> list[Any]:
    ops = ins[1:]
    branch_outs = [
        _run_sub(br, list(ops), domain, ctx.at(f"branch[{i}]"))
        for i, br in enumerate(eqn.params["branches"])
    ]
    outs = branch_outs[0]
    for other in branch_outs[1:]:
        outs = [domain.join(a, b) for a, b in zip(outs, other)]
    return outs


def _shard_map(eqn, ins: list[Any], domain: FlowDomain, ctx: FlowContext) -> list[Any]:
    body_ins = domain.enter_shard_map(eqn, ins)
    outs = _run_sub(eqn.params["jaxpr"], body_ins, domain, ctx)
    return domain.exit_shard_map(eqn, outs, ctx)


class JoinAllDomain(FlowDomain):
    """Base for may-analyses where every output derives from the inputs.

    Default transfer joins all operand values into every output — sound
    for taint-style domains where join is set-union and literals are
    bottom. Domains needing per-primitive precision override transfer.
    """

    def transfer(self, eqn, ins: list[Any]) -> list[Any]:
        acc = self.bottom()
        for v in ins:
            acc = self.join(acc, v)
        return [acc for _ in eqn.outvars]

    def bottom(self) -> Any:
        raise NotImplementedError

    def literal(self, lit) -> Any:
        return self.bottom()

    def const(self, aval, val) -> Any:
        return self.bottom()
