"""The one traffic generator. A traffic mix is a data file under
``bench/traffic/<name>.json``; its ``generator`` key names one of the
functions below and the rest are its parameters. Everything is made on the
device from a key, so the same seed gives the same inputs.

The two generators are copies of the program's own (the per-client Zipf
token pools of ``data/synthetic.py:federated_token_task`` and the Gaussian
mixture of ``make_federated_classification``), kept here so that no later
change to the program can change the traffic it is measured on.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def lm_tokens(key, *, n_clients: int, pool: int, seq: int, vocab: int,
              zipf_a: float = 1.2):
    """{"tokens": (n_clients, pool, seq) int32}: each client draws ranks
    from p(r) ~ (r+1)^-a and maps them through its own permutation of the
    vocabulary (non-iid clients)."""
    w = (jnp.arange(vocab, dtype=jnp.float32) + 1.0) ** (-zipf_a)
    cdf = jnp.cumsum(w) / jnp.sum(w)
    prime = 1_000_003 % vocab

    def one(k, cid):
        u = jax.random.uniform(k, (pool, seq))
        tok = jnp.searchsorted(cdf, u).astype(jnp.int32)
        return jnp.mod(tok * (prime + 2 * cid + 1) + cid * 7919, vocab)

    ids = jnp.arange(n_clients, dtype=jnp.int32)
    keys = jax.random.split(key, n_clients)
    return {"tokens": jax.vmap(one)(keys, ids)}


def classification(key, *, n_clients: int, samples_per_client: int, d: int,
                   n_classes: int, iid: bool, sep: float = 3.0):
    """{"x": (n, m, d), "y": (n, m)}: a Gaussian mixture with class means of
    norm ~sep, split at random (iid) or by class, so that each client holds
    a contiguous run of classes (the paper's pure non-iid split)."""
    k_mu, k_x, k_y, k_p = jax.random.split(key, 4)
    n = n_clients * samples_per_client
    mus = jax.random.normal(k_mu, (n_classes, d)) * (sep / np.sqrt(d))
    y = jax.random.randint(k_y, (n,), 0, n_classes)
    x = mus[y] + jax.random.normal(k_x, (n, d))
    if iid:
        order = jax.random.permutation(k_p, n)
    else:
        order = jnp.argsort(y, stable=True)
        blocks = order.reshape(n_clients, samples_per_client)
        order = blocks[jax.random.permutation(k_p, n_clients)].reshape(-1)
    idx = order.reshape(n_clients, samples_per_client)
    return {"x": x[idx], "y": y[idx]}


GENERATORS = {"lm_tokens": lm_tokens, "classification": classification}


def make(key, traffic: dict, **sizes):
    """The traffic's inputs; ``sizes`` are what the configuration fixes
    (vocabulary, input width, clients)."""
    params = {k: v for k, v in traffic["inputs"].items()}
    kind = params.pop("generator")
    return GENERATORS[kind](key, **params, **sizes)
