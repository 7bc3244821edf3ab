"""Plain reference of QuAFL (paper Algorithm 1) on a flat model with a
population of n clients, s polled per round.

One round, as the ``quafl`` system runs it:

1. poll s of n clients uniformly without replacement; client i, silent
   for ``elapsed`` simulated seconds, has made H = min(K, Poisson(lam_i
   elapsed)) of its K local SGD steps (batches of its own data, the rest
   masked), and holds Y = X_i - lr sum(grads);
2. every message of the round shares one rotation; client i lattice-encodes
   Y with the hint ||lr sum(grads)|| + the server's running distance
   estimate, the server decodes each against its own X_t and averages:
   X_{t+1} = (X_t + sum Q(Y_i)) / (s + 1);
3. the server encodes X_t with the hint max_i ||Q(Y_i) - X_t||, client i
   decodes it against Y_i and keeps Q(X_t) / (s + 1) + s Y_i / (s + 1);
   the server's distance estimate becomes the mean of the old one and
   that hint.

Random draws follow the round's documented key schedule: (poll, H,
exchange, local) keys from the round key; per client a local key, folded
with the step's index for its batch; from the exchange key the signs
(fold 0), the server's rounding noise (fold 1) and the clients' (fold 2,
split s ways). Client speeds: the first ``slow_frac`` of the clients are
slow.

Faults that calibration plants in place of the program: ``half_batch``
(local steps on half of each batch, the mean over it), ``unchanged`` (the
round returns its state), ``wrong_rows`` (the polled clients' new models
scattered one row off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import lattice
from bench import traffic as traffic_mod
from bench.precision import einsum_at, rotation_operand


def _flatten(params):
    return jnp.concatenate([params[k].reshape(-1) for k in sorted(params)])


def _unflatten(slices, shapes, vec):
    return {k: vec[a:b].reshape(shapes[k]) for k, a, b in slices}


def make_round(cfg, traffic, model, slices, precision, fault):
    f = cfg["fed"]
    n, s, K, B = f["n_clients"], f["s"], traffic["local_steps"], traffic["batch"]
    lr, bits, dt = f["lr"], f["bits"], f["swt"] + f["sit"]
    shapes = model.shapes(cfg)
    ein = einsum_at(precision)
    rot = functools.partial(lattice.rotate,
                            operand=rotation_operand(precision))
    lam = np.full(n, f["lam_fast"], np.float32)
    lam[: int(round(f["slow_frac"] * n))] = f["lam_slow"]
    lam = jnp.asarray(lam)

    def grad(x, batch):
        return jax.grad(lambda v: model.loss(
            cfg, _unflatten(slices, shapes, v), batch, ein))(x)

    def local(x, data_i, h, key):
        acc = jnp.zeros_like(x)
        for q in range(K):
            ix = jax.random.randint(jax.random.fold_in(key, q), (B,), 0,
                                    data_i["y"].shape[0])
            if fault == "half_batch":
                ix = ix[: B // 2]
            g = grad(x, {k: v[ix] for k, v in data_i.items()})
            act = (q < h).astype(jnp.float32)
            x, acc = x - lr * act * g, acc + act * g
        return acc

    def one_round(state, key, all_data):
        server, clients, last, t_sim, dist = state
        k_sel, k_h, k_q, k_loc = jax.random.split(key, 4)
        idx = jax.random.choice(k_sel, n, (s,), replace=False)
        elapsed = t_sim + dt - last[idx]
        h = jnp.minimum(jax.random.poisson(k_h, lam[idx] * elapsed),
                        K).astype(jnp.int32)
        cl = clients[idx]
        data = jax.tree_util.tree_map(lambda a: a[idx], all_data)
        acc = jax.vmap(local)(cl, data, h, jax.random.split(k_loc, s))
        prog = lr * 1.0 * acc
        y = cl - prog
        if fault == "unchanged":
            y = cl

        d = server.shape[0]
        d_pad = lattice.padded(d)
        signs = lattice.signs_of(jax.random.fold_in(k_q, 0), d_pad)
        u_srv = jax.random.uniform(jax.random.fold_in(k_q, 1), (1, d_pad),
                                   jnp.float32)
        u_cl = jax.vmap(lambda k: jax.random.uniform(k, (d_pad,),
                                                     jnp.float32))(
            jax.random.split(jax.random.fold_in(k_q, 2), s))
        hints = jnp.linalg.norm(prog, axis=1) + dist + 1e-8
        g_up = lattice.gamma(hints, jnp.linalg.norm(y, axis=1), d, bits=bits)
        y_rot = rot(lattice.pad2(y, d_pad), signs)
        codes = lattice.quantize(y_rot, u_cl, g_up, bits)
        srv_rot = rot(lattice.pad2(server[None], d_pad), signs)
        qy_rot = lattice.snap(codes, srv_rot, g_up, bits)
        hint_srv = jnp.max(jnp.linalg.norm(qy_rot - srv_rot, axis=1)) + 1e-8
        g_dn = lattice.gamma(hint_srv[None], jnp.linalg.norm(server)[None], d,
                             bits=bits)
        qx_rot = lattice.snap(lattice.quantize(srv_rot, u_srv, g_dn, bits),
                              y_rot, g_dn, bits)
        srv_new = rot((srv_rot[0] + jnp.sum(qy_rot, 0))[None] / (s + 1),
                      signs, inverse=True)[0, :d]
        cl_new = rot(qx_rot / (s + 1) + s * y_rot / (s + 1), signs,
                     inverse=True)[:, :d]
        rel = jnp.mean(jnp.linalg.norm(qy_rot - y_rot, axis=1)
                       / (jnp.linalg.norm(y_rot, axis=1) + 1e-9))
        if fault == "unchanged":
            srv_new, cl_new = server, cl
        t_new = t_sim + dt
        rows = jnp.roll(idx, 1) if fault == "wrong_rows" else idx
        state = (srv_new, clients.at[rows].set(cl_new),
                 last.at[rows].set(t_new), t_new,
                 0.5 * dist + 0.5 * hint_srv)
        return state, rel

    return one_round


def run(cfg, traffic, model, slices, *, k_weights, k_data, k_run, rounds,
        precision="f32", fault=None):
    """The readings of the first ``rounds`` rounds (the system's first
    chunk)."""
    f = cfg["fed"]
    n = f["n_clients"]
    one_round = make_round(cfg, traffic, model, slices, precision, fault)

    @jax.jit
    def replay(k_w, k_d, key):
        x0 = _flatten(model.weights(cfg, k_w))
        data = traffic_mod.make(k_d, traffic, n_clients=n, d=cfg["d_in"],
                                n_classes=cfg["n_classes"])
        state = (x0, jnp.tile(x0[None], (n, 1)), jnp.zeros((n,)),
                 jnp.zeros(()), jnp.ones(()) * 1e-3)

        def body(carry, _):
            key, st = carry
            key, sub = jax.random.split(key)
            st, rel = one_round(st, sub, data)
            return (key, st), rel

        (_, st), rels = jax.lax.scan(body, (key, state), None, length=rounds)
        server, clients = st[0], st[1]
        return rels, {
            "server": {k: jnp.linalg.norm(server[a:b] - x0[a:b])
                       for k, a, b in slices},
            "clients": {k: jnp.linalg.norm(clients[:, a:b] - x0[None, a:b],
                                           axis=1)
                        for k, a, b in slices}}

    rels, change = jax.device_get(replay(k_weights, k_data, k_run))
    return {"quant_err": list(rels), "change": change}
