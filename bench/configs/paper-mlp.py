"""Plain float32 reference of the paper's MLP (App. A.3) and the weights
both sides start from: relu(x W1 + b1) W2 + b2 under a mean softmax
cross-entropy. Matrix products go through ``ein``, which the caller picks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def shapes(cfg):
    d, h, c = cfg["d_in"], cfg["d_hidden"], cfg["n_classes"]
    return {"b1": (h,), "b2": (c,), "w1": (d, h), "w2": (h, c)}


def weights(cfg, key):
    """Normal weights scaled by 1/sqrt(fan in), zero biases."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        if name.startswith("b"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32)
                         / np.float32(math.sqrt(shape[0])))
    return out


def loss(cfg, params, batch, ein):
    h = jax.nn.relu(ein("bd,dh->bh", batch["x"], params["w1"]) + params["b1"])
    logits = ein("bh,hc->bc", h, params["w2"]) + params["b2"]
    tgt = jnp.take_along_axis(logits, batch["y"][:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - tgt)
