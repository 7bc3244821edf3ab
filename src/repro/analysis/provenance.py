"""Wire provenance marks: a zero-cost identity primitive for message sites.

``wire_mark(x, channel=..., part=..., codec=...)`` is an identity on
``x`` that survives into the traced jaxpr, so the wire-truth audit
(``analysis/wire.py``) can locate every value the code *claims* is a
wire message and cross-check its traced dtype/shape against the codec's
machine-readable declaration. It lowers to its operand (XLA sees nothing)
and vmap rewrites ``batched=False`` to ``True`` so per-message encodes
vmapped over the message axis stay honestly described.

This module is deliberately import-light: ``repro.compression`` imports
it at module load, so it must not pull the analyzers (or jax.numpy-heavy
code) in transitively.
"""

from __future__ import annotations

from jax.extend.core import Primitive
from jax.interpreters import batching, mlir

MARK_PRIM_NAME = "wire_mark"

wire_mark_p = Primitive(MARK_PRIM_NAME)
wire_mark_p.def_impl(lambda x, **_: x)
wire_mark_p.def_abstract_eval(lambda x, **_: x)
mlir.register_lowering(wire_mark_p, lambda ctx, x, **_: [x])


def _batch_rule(args, dims, **params):
    (x,), (d,) = args, dims
    return wire_mark_p.bind(x, **{**params, "batched": True}), d


batching.primitive_batchers[wire_mark_p] = _batch_rule

# part names a role inside one message; side-channel rows (charged at 32
# bits each by the codec declaration) are everything except the payload.
PAYLOAD_PARTS = ("codes", "idx", "vals")
SIDE_PARTS = ("gamma", "levels", "scale")


def wire_mark(x, *, channel: str, part: str, codec: str,
              batched: bool = False, d: int = 0):
    """Mark ``x`` as the ``part`` of a ``channel`` message of ``codec``.

    channel: "up" | "down" — uplink (client→server) or downlink.
    part: "codes"/"idx"/"vals" payload, or a named side-channel row.
    batched: True when the leading axis of ``x`` is a message batch
      (one message per row); vmap sets this automatically.
    d: the model/leaf dimension this message encodes (0 = unknown). Mesh
      exchanges ship PER-LEAF messages whose element counts differ from
      the flat-model declaration; recording the encode-site dimension lets
      the wire-truth audit rebuild the codec's declaration at exactly this
      granularity instead of guessing.
    """
    return wire_mark_p.bind(x, channel=channel, part=part, codec=codec,
                            batched=batched, d=int(d))


def observe_wire(x, **kwargs):
    """Record a mark without re-routing the value (returns None).

    Use where the live value must keep its dtype but the *wire* form is a
    cast (e.g. uint32 working codes whose wire container is uint8): pass
    the cast value here; the mark stays in the jaxpr, XLA dead-codes it.
    """
    wire_mark(x, **kwargs)


def iter_marks(closed):
    """Yield (eqn, aval, params) for every wire_mark in a closed jaxpr."""
    from repro.analysis.jaxpr import iter_eqns

    for eqn in iter_eqns(closed):
        if eqn.primitive.name == MARK_PRIM_NAME:
            yield eqn, eqn.invars[0].aval, dict(eqn.params)
