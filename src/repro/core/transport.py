"""Leaf-wise codec transport helpers for the DISTRIBUTED QuAFL train step.

The simulation core (repro.core.quafl) works on one flat vector; on a mesh
we encode per parameter leaf instead (each leaf flattens to its own vector,
rotation blocks never cross leaves). Algebraically this is still a valid
instance of the blockwise lattice quantizer — the rotation is block-diagonal
either way — and it keeps every encode/decode local to the shards that own
the leaf. The helpers are CODEC-AGNOSTIC: any
:mod:`repro.compression.codecs` object with
``encode(key, x, hint) / decode(key, msg, ref) / message_bits(d)`` rides
them, and messages are opaque pytrees.

The aggregation strategies themselves (fp32 psum vs. packed-code
all-gather vs. the reduce-scatter fusion) are the pluggable
:class:`repro.compression.transports.Transport` registry; the vmap-level
legacy compositions (dequant_psum / code_allgather) live in
``repro.launch.steps`` and the shard_map family in
``repro.core.exchange_local``.

The per-leaf encode/decode math runs through the compression-pipeline
backend selected by ``FedConfig.kernel_backend`` (lattice codecs delegate
to repro.compression.pipeline): each Enc is one fused rotate+round+wrap
pass and each Dec one fused rotate-ref+snap+inverse-rotate pass — no
materialized rotation intermediates. The fully rotated-space restructuring
(one rotation per vector per ROUND) lives in repro.core.exchange_local for
the shard-local transports and repro.compression.pipeline.quafl_round for
the flat simulator.
"""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from repro.compression.codecs import LatticeMsg
from repro.utils.tree import fold_in_str


def leaf_dist(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    """Per-leaf L2 distance between two flat-dict trees."""
    return {k: jnp.linalg.norm((a[k] - b[k]).astype(jnp.float32).ravel())
            for k in a}


def tree_encode(quant, key, tree: Dict[str, Any],
                hints: Dict[str, jnp.ndarray]) -> Dict[str, LatticeMsg]:
    out = {}
    for k, v in tree.items():
        out[k] = quant.encode(fold_in_str(key, k),
                              v.astype(jnp.float32).ravel(), hints[k] + 1e-12)
    return out


def tree_decode(quant, key, msgs: Dict[str, LatticeMsg],
                ref: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    out = {}
    for k, m in msgs.items():
        flat = quant.decode(fold_in_str(key, k), m,
                            ref[k].astype(jnp.float32).ravel())
        out[k] = flat.reshape(ref[k].shape).astype(ref[k].dtype)
    return out


def tree_bits(quant, tree: Dict[str, Any]) -> int:
    import numpy as np
    return int(sum(quant.message_bits(int(np.prod(v.shape)))
                   for v in tree.values()))
