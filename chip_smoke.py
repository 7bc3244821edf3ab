"""Smoke run of the federated round on a TPU, through the repo's entry points.

    python chip_smoke.py

  1. The LM round. The ``spmd`` registry algorithm (``make_algorithm``, as
     ``launch/train.py`` builds it) trains llama3.2-1b at its published
     widths on a (1, 1) mesh with ``kernel_backend="pallas"``: three rounds
     through ``simulate()``. ``server_loss`` and ``quant_err`` stay finite,
     ``quant_err`` far below 1, and the compiled round holds the Pallas
     kernels (``tpu_custom_call``).
  2. The kernels against their reference. From one state and key, one
     round with ``kernel_backend="pallas"`` and one with ``"jnp"`` give the
     same server params (rtol = atol = 2e-5). Each backend's rotation is
     also held to a float64 rotation computed on the host.
  3. The paper's own path. ``quafl`` on the paper's MLP with n = 300
     clients (LEAF scale) and ``kernel_backend="pallas"`` runs scanned
     chunks of ``simulate()``; the loss stays finite.

Sizing. Every width is llama3.2-1b's (d_model 2048, 32 query / 8 KV heads
of 64, d_ff 8192, vocab 128256). The state holds the server and one client
copy in fp32, so depth is cut to LM_LAYERS of 16: the deepest
round whose compiled ``memory_analysis()`` for a v5e leaves headroom under
its 15.75 GiB (see CHANGES.md). Rematerialization is on
(``SpmdAlgorithm.remat``). Each client takes LM_LOCAL_STEPS local steps on
LM_BATCH x LM_SEQ tokens. Weights and tokens are random, made from SEED.

The script fails (non-zero exit, no result line) where JAX finds no TPU.
Its last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
from functools import partial
from pathlib import Path

SEED = 0
LM_LAYERS = 4
LM_BATCH = 4
LM_SEQ = 1024
LM_LOCAL_STEPS = 2
LM_LR = 0.02
LM_POOL = 16
LM_ROUNDS = 3
KERNEL_TOL = 2e-5          # tests/test_distributed.py: pallas vs jnp
ROTATION_TOL = 1e-5        # fp32 holds ~1e-7; one bf16 pass ~4e-3
MLP_CLIENTS, MLP_SAMPLED, MLP_IN, MLP_HIDDEN, MLP_CLASSES = 300, 30, 784, 32, 10
MLP_ROUNDS, MLP_CHUNK = 12, 4


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self):
        out = (self.seconds, self.programs)
        self.seconds, self.programs = 0.0, 0
        return out


def _device_label(dev) -> str:
    return f"{dev.platform}:{dev.device_kind}:{dev.id}"


def _mem(dev, what: str):
    """``memory_stats()`` bytes in use and the peak so far."""
    s = dev.memory_stats() or {}
    log(f"[mem] {what}: {_device_label(dev)} bytes_in_use="
        f"{s.get('bytes_in_use', 'not reported')} peak_bytes_in_use="
        f"{s.get('peak_bytes_in_use', 'not reported')}")


# ---------------------------------------------------------------------------
# builders: the model, the algorithm and its params
# ---------------------------------------------------------------------------

def lm_config():
    from repro.configs import get_config
    return get_config("llama3.2-1b").replace(n_layers=LM_LAYERS)


def lm_algorithm(cfg, mesh, template, *, backend: str):
    """The ``spmd`` algorithm as ``launch/train.py:run_registry`` builds it."""
    from repro.configs.base import FedConfig
    from repro.data.synthetic import federated_token_task
    from repro.fed import make_algorithm
    from repro.models.model import lm_loss

    fed = FedConfig(n_clients=1, s=1, local_steps=LM_LOCAL_STEPS, lr=LM_LR,
                    kernel_backend=backend)
    data, batch_fn = federated_token_task(SEED, 1, LM_POOL, LM_BATCH,
                                          LM_SEQ, cfg.vocab_size)
    alg = make_algorithm("spmd", fed, loss_fn=partial(lm_loss, cfg),
                         template=template, batch_fn=batch_fn, cfg=cfg,
                         mesh=mesh, batch=LM_BATCH, seq=LM_SEQ, remat=True)
    return alg, data


def host_params(cfg):
    """Random llama params (made on the device, kept on the host so no
    phase pays device memory for them)."""
    import jax
    from repro.models.model import init_lm
    params, _ = init_lm(cfg, jax.random.PRNGKey(SEED))
    return jax.device_get(params)


def _diff_stats(a, b, tol: float):
    """{leaf: (max |a - b|, elements outside |a - b| <= tol + tol |b| (the
    ``assert_allclose`` rule; NaN counts as outside), elements,
    sum (a - b)^2, sum b^2)} of two param trees, reduced on the devices
    by one program: only the scalars come back to the host."""
    import jax
    import jax.numpy as jnp

    def one(x, y):
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        d = jnp.abs(x - y)
        return (jnp.max(jnp.where(jnp.isnan(d), jnp.inf, d)),
                jnp.sum(~(d <= tol + tol * jnp.abs(y)), dtype=jnp.int32),
                jnp.sum(d * d), jnp.sum(y * y))

    stats = jax.device_get(jax.jit(
        lambda a, b: {k: one(a[k], b[k]) for k in a})(a, b))
    return {k: (float(w), int(bad), a[k].size, float(dd), float(yy))
            for k, (w, bad, dd, yy) in stats.items()}


def _check_close(a, b, tol: float, what: str):
    """Every element of tree ``a`` within rtol = atol = ``tol`` of ``b``.
    Logs the worst leaf, every leaf with elements outside and the count,
    and raises on a miss."""
    stats = _diff_stats(a, b, tol)
    where = max(stats, key=lambda k: stats[k][0])
    bad = sum(s[1] for s in stats.values())
    n = sum(s[2] for s in stats.values())
    log(f"[check] {what}: max |diff| = {stats[where][0]:.3e} ({where}); "
        f"{bad} of {n} elements outside rtol = atol = {tol}")
    for k, st in stats.items():
        if st[1]:
            log(f"[check]   {k}: {st[1]} of {st[2]} outside, max |diff| "
                f"{st[0]:.3e}, relative L2 "
                f"{math.sqrt(st[3]) / (math.sqrt(st[4]) + 1e-30):.3e}")
    if bad:
        raise RuntimeError(f"failed check: {what}")


def precompile(clog, jobs):
    """Compile the lowered programs concurrently (XLA compiles outside the
    GIL). Each lands in the persistent compile cache, so the entry points'
    own first calls then load it instead of compiling one after another.
    Returns {name: compiled}."""
    from concurrent.futures import ThreadPoolExecutor

    def one(job):
        name, lowered = job
        t = time.perf_counter()
        compiled = lowered.compile()
        return name, compiled, time.perf_counter() - t

    clog.take()
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(jobs)) as pool:
        done = list(pool.map(one, jobs))
    wall = time.perf_counter() - t0
    secs, n = clog.take()
    for name, _, s in done:
        log(f"[compile] {name}: {s:.3f} s")
    log(f"[compile] {len(jobs)} programs in parallel: wall {wall:.3f} s, "
        f"backend compile_s={secs:.3f} summed over {n} programs")
    return {name: compiled for name, compiled, _ in done}


def _lower_round(alg, state, data, key):
    # _round is a jitted method: lower it with the instance as its first arg
    return type(alg)._round.lower(alg, state, data, key)


# ---------------------------------------------------------------------------
# the phases
# ---------------------------------------------------------------------------

def phase_lm_round(dev, clog, alg, data, loss_fn, params, compiled):
    import jax
    from repro.fed import simulate

    n_calls = compiled["round pallas"].as_text().count("tpu_custom_call")
    if not n_calls:
        raise RuntimeError("compiled round holds no tpu_custom_call")
    log(f"[lm] compiled pallas round holds {n_calls} tpu_custom_call sites")
    marks = {"t": time.perf_counter()}
    round_s = []

    def eval_fn(server):
        jax.block_until_ready(server)
        now = time.perf_counter()
        round_s.append(now - marks["t"])
        loss = float(loss_fn(server))
        marks["t"] = time.perf_counter()
        return {"server_loss": loss}

    def on_row(row):
        log(f"[lm] round {row['round']} server_loss={row['server_loss']:.6f} "
            f"quant_err={row['quant_err']:.3e} "
            f"round_s={round_s[-1]:.3f} ({_device_label(dev)})")

    trace = simulate(alg, params, data, jax.random.PRNGKey(SEED + 2),
                     rounds=LM_ROUNDS, eval_every=1, eval_fn=eval_fn,
                     on_row=on_row)
    secs, n = clog.take()
    log(f"[lm] first round (init + load compiled + run) {round_s[0]:.3f} s; "
        f"steady rounds {', '.join(f'{s:.3f}' for s in round_s[1:])} s; "
        f"compile_s={secs:.3f} over {n} programs in the run "
        f"({_device_label(dev)})")
    for row in trace.rows:
        if not math.isfinite(row["server_loss"]):
            raise RuntimeError(f"server_loss not finite: {row}")
        if not (math.isfinite(row["quant_err"]) and row["quant_err"] < 1e-2):
            raise RuntimeError(f"quant_err not finite and far below 1: {row}")
    _mem(dev, "after the LM rounds")


def _rotation_gaps(dev):
    """Each backend's rotation of one vector vs float64 on the host."""
    import jax
    import numpy as np
    from repro.compression.pipeline import get_backend
    from repro.compression.rotation import _signs, hadamard_matrix
    from repro.kernels.exchange import block_geometry

    d = 1 << 22
    key = jax.random.PRNGKey(SEED + 3)
    x = jax.random.normal(key, (1, d), np.float32)
    signs = _signs(jax.random.fold_in(key, 1), d)
    b, _, r, c, nb = block_geometry(d)
    xs = np.asarray(x, np.float64)[0] * np.asarray(signs, np.float64)
    ref = np.einsum("ij,bjk,kl->bil", hadamard_matrix(r).astype(np.float64),
                    xs.reshape(nb, r, c), hadamard_matrix(c).astype(np.float64)
                    ).reshape(-1) / np.sqrt(b)
    scale = float(np.max(np.abs(ref)))
    for name in ("pallas", "jnp"):
        y = np.asarray(get_backend(name).rotate(x, signs), np.float64)[0]
        gap = float(np.max(np.abs(y - ref))) / scale
        log(f"[rotation] {name}: max |rot - rot_f64| / max |rot_f64| = "
            f"{gap:.3e} ({_device_label(dev)})")
        if gap > ROTATION_TOL:
            raise RuntimeError(f"{name} rotation is not fp32: {gap:.3e}")


def phase_kernels_vs_ref(dev, clog, algs, data, params):
    import jax
    _rotation_gaps(dev)
    key = jax.random.PRNGKey(SEED + 4)
    servers = {}
    for name, alg in algs.items():
        state, m = alg.round(alg.init(params), data, key)
        servers[name] = jax.device_get(state.train.server)
        log(f"[kernels] {name} round quant_err={float(m['quant_err']):.3e}")
        del state, m
    secs, n = clog.take()
    log(f"[kernels] compile_s={secs:.3f} over {n} programs "
        f"({_device_label(dev)})")
    _check_close(servers["pallas"], servers["jnp"], KERNEL_TOL,
                 "server params, pallas vs jnp")
    _mem(dev, "after the kernel comparison")


def phase_paper_mlp(dev, clog):
    import jax
    from repro.configs.base import FedConfig
    from repro.data import make_federated_classification
    from repro.data.synthetic import client_batch
    from repro.fed import make_algorithm, simulate
    from repro.models.mlp import init_mlp_classifier, mlp_loss

    fed = FedConfig(n_clients=MLP_CLIENTS, s=MLP_SAMPLED, local_steps=4,
                    lr=0.1, kernel_backend="pallas")
    part, test = make_federated_classification(SEED, MLP_CLIENTS, d=MLP_IN,
                                               n_classes=MLP_CLASSES)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(SEED), MLP_IN,
                                     MLP_HIDDEN, MLP_CLASSES)
    alg = make_algorithm("quafl", fed, loss_fn=mlp_loss, template=params0,
                         batch_fn=lambda dd, k: client_batch(k, dd, 32))

    def eval_fn(p):
        loss, aux = mlp_loss(p, test)
        return {"loss": float(loss), "acc": float(aux["acc"])}

    def on_row(row):
        log(f"[mlp] round {row['round']} loss={row['loss']:.6f} "
            f"acc={row['acc']:.4f} quant_err={row['quant_err']:.3e}")

    clog.take()
    t0 = time.perf_counter()
    trace = simulate(alg, params0, part, jax.random.PRNGKey(SEED + 5),
                     rounds=MLP_ROUNDS, eval_every=MLP_CHUNK, eval_fn=eval_fn,
                     on_row=on_row, scan_chunk=MLP_CHUNK)
    wall = time.perf_counter() - t0
    secs, n = clog.take()
    if trace.engine != "scanned":
        raise RuntimeError(f"expected the scanned engine, ran {trace.engine}")
    for row in trace.rows:
        if not (math.isfinite(row["loss"]) and math.isfinite(row["quant_err"])):
            raise RuntimeError(f"paper-mlp loss not finite: {row}")
    log(f"[mlp] n={MLP_CLIENTS} s={MLP_SAMPLED} {trace.rounds} rounds in "
        f"chunks of {MLP_CHUNK}: wall {wall:.3f} s incl. compile_s="
        f"{secs:.3f} over {n} programs ({_device_label(dev)})")
    _mem(dev, "after the paper MLP")


def run_one_chip(dev, clog):
    import jax
    from repro.data.synthetic import lm_token_stream
    from repro.models.model import lm_loss
    from repro.utils.compat import make_mesh

    cfg = lm_config()
    mesh = make_mesh((1, 1), ("data", "model"))
    params = host_params(cfg)
    n_params = sum(v.size for v in params.values())
    log(f"[lm] llama3.2-1b widths, {cfg.n_layers} of 16 layers, "
        f"{n_params} params; batch {LM_BATCH} x seq {LM_SEQ}, "
        f"K={LM_LOCAL_STEPS}, remat on")
    algs, data = {}, None
    for backend in ("pallas", "jnp"):
        algs[backend], data = lm_algorithm(cfg, mesh, params,
                                           backend=backend)
    eval_toks = lm_token_stream(jax.random.PRNGKey(SEED + 1), LM_BATCH,
                                LM_SEQ, cfg.vocab_size)
    loss_fn = jax.jit(lambda p: lm_loss(cfg, p, {"tokens": eval_toks})[0])
    state = algs["pallas"].init(params)
    key = jax.random.PRNGKey(SEED)
    jobs = [(f"round {b}", _lower_round(a, state, data, key))
            for b, a in algs.items()]
    jobs.append(("eval loss", loss_fn.lower(state.train.server)))
    del state
    compiled = precompile(clog, jobs)
    phase_lm_round(dev, clog, algs["pallas"], data, loss_fn, params,
                   compiled)
    phase_kernels_vs_ref(dev, clog, algs, data, params)
    del algs, data, compiled
    phase_paper_mlp(dev, clog)


# ---------------------------------------------------------------------------

def main() -> int:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU found: JAX's first device is {dev.platform} "
              f"({dev.device_kind}); this smoke run needs a TPU",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.utils.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clog = CompileLog()
    log(f"[setup] {len(devices)} x {dev.device_kind} ({dev.platform}); "
        f"jax {jax.__version__}; compile cache {cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    t0 = time.perf_counter()
    run_one_chip(dev, clog)
    log(f"[setup] wall {time.perf_counter() - t0:.3f} s; persistent cache "
        f"hits {clog.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
