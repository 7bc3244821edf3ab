"""Plain reference of one QuAFL round as the ``spmd`` system runs it, for
n client slots of a language model (paper Algorithm 1 with every client
polled each round):

1. each client draws K rows of its token pool per step, takes
   H = min(K, Poisson(lam (swt + sit))) SGD steps (the rest masked), and
   holds Y;
2. each client sends every parameter leaf lattice-encoded against a hint
   ||Y - X_client||; the server decodes each against its own X_t and
   averages: X_{t+1} = (X_t + sum_i Q(Y_i)) / (n + 1);
3. the server sends X_t, lattice-encoded with the hint 2 max_i ||Q(Y_i) -
   X_t||; client i decodes it against its previous model and keeps
   Q(X_t) / (n + 1) + n Y_i / (n + 1).

The random draws follow the round's documented key schedule: the round key
splits into (batch, step) keys, the step key into (H, exchange, local)
keys, each client's exchange key is split from fold_in(exchange, 1), each
leaf's from fold_in(client key, crc32(leaf name)), the server's from
fold_in(exchange, n + 7); a message key splits into (rotation signs,
rounding noise). The model's arithmetic is the configuration's reference
(``bench/configs/<name>.py``) at the precision asked for.

Gradients are taken one row of the batch at a time and summed, so that the
full-precision reference fits next to the three model copies it keeps.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp

from bench import lattice
from bench import traffic as traffic_mod
from bench.precision import einsum_at, rotation_operand


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _encode_decode(key, x, hint, ref, bits, rot):
    """Q(x) decoded against ref, for one flattened leaf; ``rot`` rounds the
    rotations' inputs (a control) or is None."""
    d = x.size
    d_pad = lattice.padded(d)
    k_rot, k_rnd = jax.random.split(key)
    signs = lattice.signs_of(k_rot, d_pad)
    u = jax.random.uniform(k_rnd, (d_pad,), jnp.float32)
    xf = x.astype(jnp.float32).reshape(-1)
    g = lattice.gamma(hint + 1e-12, jnp.linalg.norm(xf), d, bits=bits)[None]
    codes = lattice.quantize(
        lattice.rotate(lattice.pad2(xf[None], d_pad), signs, operand=rot),
        u[None], g, bits)
    w = lattice.rotate(lattice.pad2(ref.reshape(1, -1), d_pad), signs,
                       operand=rot)
    q = lattice.snap(codes, w, g, bits)
    return lattice.rotate(q, signs, inverse=True,
                          operand=rot)[0, :d].reshape(x.shape)


def _grad(cfg, model, ein, p, toks, fault):
    """Gradient of the mean next-token loss over the (b, T) rows."""
    if fault == "half_batch":
        toks = toks[: max(1, toks.shape[0] // 2)]
    b, t = toks.shape

    def body(acc, row):
        g = jax.grad(partial(model.row_loss, cfg, ein=ein))(p, row)
        return jax.tree_util.tree_map(jnp.add, acc, g), None

    acc, _ = jax.lax.scan(body, jax.tree_util.tree_map(jnp.zeros_like, p),
                          toks)
    return jax.tree_util.tree_map(lambda g: g / (b * (t - 1)), acc)


def make_round(cfg, traffic, model, precision, fault):
    f = cfg["fed"]
    K, B, bits, lr = (traffic["local_steps"], traffic["batch"], f["bits"],
                      f["lr"])
    rate = f["lam_fast"] * (f["swt"] + f["sit"])
    ein, rot = einsum_at(precision), rotation_operand(precision)

    def one_round(server, clients, pool, key):
        n = len(clients)
        k_b, k_r = jax.random.split(key)
        idx = jax.random.randint(k_b, (n, K, B), 0, pool.shape[1])
        k_h, k_q, k_loc = jax.random.split(k_r, 3)
        h = jnp.minimum(jax.random.poisson(k_h, jnp.float32(rate), (n,)), K)
        q_keys = jax.random.split(jax.random.fold_in(k_q, 1), n)
        ys, qys, qerr = [], [], 0.0
        for i, cp in enumerate(clients):
            p = cp
            for q in range(K):
                g = _grad(cfg, model, ein, p, pool[i][idx[i, q]], fault)
                act = (q < h[i]).astype(jnp.float32)
                p = {k: p[k] - lr * act * g[k] for k in p}
            if fault == "unchanged":
                p = cp
            ys.append(p)
            qy = {k: _encode_decode(_leaf_key(q_keys[i], k), p[k],
                                    jnp.linalg.norm(p[k] - cp[k]),
                                    server[k], bits, rot) for k in p}
            qys.append(qy)
            qerr = qerr + sum(jnp.sum((qy[k] - p[k]) ** 2) for k in p)
        new_server = {k: (server[k] + sum(qy[k] for qy in qys)) / (n + 1)
                      for k in server}
        hints = {k: 2.0 * jnp.max(jnp.stack(
            [jnp.linalg.norm(qy[k] - server[k]) for qy in qys]))
            for k in server}
        k_srv = jax.random.fold_in(k_q, n + 7)
        new_clients = []
        for cp, y in zip(clients, ys):
            qx = {k: _encode_decode(_leaf_key(k_srv, k), server[k], hints[k],
                                    cp[k], bits, rot) for k in server}
            new_clients.append({k: qx[k] / (n + 1) + n * y[k] / (n + 1)
                                for k in server})
        if fault == "unchanged":
            new_server, new_clients = server, list(clients)
        return new_server, new_clients, jnp.sqrt(qerr / n)

    return jax.jit(one_round, donate_argnums=(0, 1))


def run(cfg, traffic, model, *, k_weights, k_data, k_run, precision="f32",
        fault=None):
    """The readings of the first ``check_rounds`` rounds, as the system's
    set-up records them."""
    n = cfg["fed"]["n_clients"]
    w0 = jax.jit(lambda k: model.weights(cfg, k))
    pool = jax.jit(lambda k: traffic_mod.make(
        k, traffic, n_clients=n, vocab=cfg["vocab_size"]))(k_data)["tokens"]
    one_round = make_round(cfg, traffic, model, precision, fault)

    @jax.jit
    def change(server, clients, k):
        start = model.weights(cfg, k)
        out = {"server": {k_: jnp.linalg.norm(server[k_] - start[k_])
                          for k_ in start}}
        for i, c in enumerate(clients):
            out[f"client{i}"] = {k_: jnp.linalg.norm(c[k_] - start[k_])
                                 for k_ in start}
        return out

    server = w0(k_weights)
    clients = [w0(k_weights) for _ in range(n)]
    key, quant_err, update = k_run, [], None
    for r in range(traffic["check_rounds"]):
        key, sub = jax.random.split(key)
        server, clients, qe = one_round(server, clients, pool, sub)
        quant_err.append(qe)
        if r == 0:
            update = change(server, clients, k_weights)["server"]
    last = change(server, clients, k_weights)
    return jax.device_get({"quant_err": quant_err, "update": update,
                           "change": last})
