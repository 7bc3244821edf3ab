"""SPMD divergence detection on the abstract mesh.

``shard_map`` gives every device its own python-identical program over
different data; a value inside the body is *divergent* over a mesh axis
when devices along that axis may hold different values. Committing such a
value through an output spec that does not carry the axis (``P()`` —
"replicated") silently publishes device 0's copy: state that should be a
cross-client aggregate becomes one client's local value. That bug class is
invisible to tests that only check shapes/finiteness — this analyzer makes
it a gate violation.

:class:`DivergenceDomain` runs on the flow engine with values =
``frozenset`` of mesh axis names a value may vary over (∅ = replicated;
the distinguished ``"*"`` = unknown provenance, treated as varying over
everything):

* entering a ``shard_map``, each body input varies over the axes its
  ``in_specs`` shard it along (different devices see different blocks);
* ``axis_index(a)`` introduces variance over ``a``; ``psum``/``pmax``/
  ``pmin``/``all_gather`` *remove* the reduced/gathered axes (every device
  ends with the same aggregate); ``psum_scatter`` and ``ppermute`` keep or
  introduce the axis (devices end with different shards);
* everything else joins its operands (set union) — sound for elementwise
  and structural ops;
* exiting the ``shard_map``, an output still varying over an axis that its
  ``out_specs`` entry does not carry is reported as a divergence escape.

:func:`check_divergence` wraps the run and returns the violations.
"""

from __future__ import annotations

from typing import FrozenSet, List

from repro.analysis.flow import FlowContext, JoinAllDomain, analyze_flow
from repro.analysis.jaxpr import Violation

Axes = FrozenSet[str]

_UNKNOWN = "*"

# collectives that make their result identical across the named axes
_RESOLVING = {"psum", "pmax", "pmin", "all_gather", "all_reduce"}
# collectives whose result still differs per device along the axis
_SHARDING = {"psum_scatter", "reduce_scatter", "ppermute"}


def _eqn_axes(eqn) -> Axes:
    ax = eqn.params.get("axes", None)
    if ax is None:
        ax = eqn.params.get("axis_name", ())
    if isinstance(ax, (str, int)):
        ax = (ax,)
    return frozenset(str(a) for a in ax)


def _spec_axes(spec) -> Axes:
    """Mesh axes mentioned by one in_specs/out_specs ``PartitionSpec``
    (entries are None, an axis name, or a tuple of axis names)."""
    out = set()
    for axes in spec:
        if axes is None:
            continue
        if isinstance(axes, (str, int)):
            out.add(str(axes))
        else:
            out.update(str(a) for a in axes)
    return frozenset(out)


class DivergenceDomain(JoinAllDomain):
    """May-vary axes per value; join = union."""

    def top(self, aval) -> Axes:
        return frozenset({_UNKNOWN})

    def bottom(self) -> Axes:
        return frozenset()

    def join(self, a: Axes, b: Axes) -> Axes:
        return a | b

    def transfer(self, eqn, ins: List[Axes]) -> List[Axes]:
        name = eqn.primitive.name
        if name == "axis_index":
            return [frozenset({str(eqn.params["axis_name"])})
                    for _ in eqn.outvars]
        if name in _RESOLVING:
            resolved = _eqn_axes(eqn)
            return [v - resolved for v in ins][:len(eqn.outvars)] \
                or [self.bottom() for _ in eqn.outvars]
        if name in _SHARDING:
            extra = _eqn_axes(eqn)
            return [v | extra for v in ins][:len(eqn.outvars)] \
                or [extra for _ in eqn.outvars]
        return super().transfer(eqn, ins)

    def enter_shard_map(self, eqn, ins: List[Axes]) -> List[Axes]:
        in_specs = eqn.params["in_specs"]
        return [v | _spec_axes(spec) for v, spec in zip(ins, in_specs)]

    def exit_shard_map(self, eqn, outs: List[Axes],
                       ctx: FlowContext) -> List[Axes]:
        out_specs = eqn.params["out_specs"]
        mesh_axes = frozenset(str(a) for a in eqn.params["mesh"].axis_names)
        mapped = []
        for i, (v, spec) in enumerate(zip(outs, out_specs)):
            carried = _spec_axes(spec)
            escaped = (v & (mesh_axes | {_UNKNOWN})) - carried
            if escaped:
                what = ("unknown-provenance value" if _UNKNOWN in escaped
                        else f"value varying over mesh axes "
                             f"{sorted(escaped)}")
                ctx.facts.append(Violation(
                    "spmd-divergence", ctx.where,
                    f"shard_map output {i} commits a {what} through "
                    f"out_specs {spec} — device 0's copy "
                    f"is silently published as replicated state"))
            # outside the mesh the committed value is what the spec says
            mapped.append(v - mesh_axes - {_UNKNOWN})
        return mapped


def check_divergence(closed, where: str) -> List[Violation]:
    """Flag divergent values escaping any ``shard_map`` in ``closed`` as
    replicated state. Top-level inputs are global (replicated) arrays."""
    dom = DivergenceDomain()
    inputs = [dom.bottom() for _ in closed.jaxpr.invars]
    ctx = FlowContext(path=(where,))
    res = analyze_flow(closed, dom, inputs=inputs, ctx=ctx)
    return [f for f in ctx.facts if isinstance(f, Violation)]
