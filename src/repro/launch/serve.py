"""Serving driver: batched requests against a (reduced) model via the
ServeEngine. Demonstrates the decode path the decode_32k/long_500k dry-run
shapes lower.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --requests 12

With ``--from-algo NAME`` the served weights are the ``eval_params`` of a
short federated run of that registry algorithm (quafl, fedavg, ...) instead
of a fresh init — serving is inference of the federated result, and the
unified protocol makes any algorithm's outcome servable the same way:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --from-algo quafl --algo-rounds 5 --requests 4
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.models.model import init_lm
from repro.serving import Request, ServeEngine
from repro.utils.cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--from-algo", default="",
                    help="registry algorithm whose eval_params to serve "
                         "(quafl|fedavg|fedbuff|sequential|...)")
    ap.add_argument("--algo-rounds", type=int, default=5)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    if cfg.encdec:
        raise SystemExit("enc-dec serving demo not wired in this CLI")
    key = jax.random.PRNGKey(args.seed)
    params, _ = init_lm(cfg, key)
    if args.from_algo:
        from functools import partial

        from repro.configs.base import FedConfig
        from repro.data.synthetic import federated_token_task
        from repro.fed import make_algorithm, simulate
        from repro.models.model import lm_loss

        fed = FedConfig(n_clients=4, s=4, local_steps=2, lr=0.05)
        pool, batch, seq = 8, 2, 32
        data, batch_fn = federated_token_task(args.seed, fed.n_clients,
                                              pool, batch, seq,
                                              cfg.vocab_size)

        alg = make_algorithm(args.from_algo, fed, loss_fn=partial(lm_loss,
                                                                  cfg),
                             template=params, batch_fn=batch_fn)
        trace = simulate(alg, params, data, jax.random.fold_in(key, 1),
                         rounds=args.algo_rounds, eval_every=0)
        print(f"serving eval_params of a {args.from_algo} run "
              f"({trace.rounds} rounds, "
              f"sim_t={float(trace.final_state.sim_time):.0f})")
        eng = ServeEngine.from_algorithm(cfg, alg, trace.final_state,
                                         max_batch=args.max_batch,
                                         max_seq=128,
                                         temperature=args.temperature)
    else:
        eng = ServeEngine(cfg, params, max_batch=args.max_batch, max_seq=128,
                          temperature=args.temperature)
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = int(rng.integers(4, 24))
        prompt = rng.integers(1, cfg.vocab_size, plen).tolist()
        eng.submit(Request(prompt=prompt, max_new_tokens=args.max_new))
    t0 = time.time()
    done = eng.run(key)
    dt = time.time() - t0
    total = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s)")
    for i, r in enumerate(done[:4]):
        print(f"  req{i}: prompt[{len(r.prompt)}] -> {r.out_tokens}")


if __name__ == "__main__":
    main()
