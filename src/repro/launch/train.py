"""End-to-end training driver (runs REAL steps, not a dry-run).

On this container it runs reduced/small variants on the single CPU device;
on a pod, point --mesh-data/--mesh-model at the real topology and the same
program distributes via GSPMD.

EVERY algorithm — including the mesh-sharded SPMD path — now runs through
the unified registry (``repro.fed``) and the generic ``simulate()`` harness
with the standardized metrics schema (``sim_time``, ``bits_up``,
``bits_down``, ``h_steps_mean``, ``quant_err``):

  * ``--algo spmd`` (default) — the distributed train step wrapped by
    ``repro.launch.spmd.SpmdAlgorithm``: clients live on mesh slots, the
    quantized exchange runs as mesh collectives, and the rounds land in the
    same Trace format as the simulator algorithms.
  * ``--algo quafl|fedavg|fedbuff|fedbuff_device|sequential|...`` — any
    registry server variant; the protocol only sees a params pytree, so any
    zoo architecture trains under any algorithm.

``--scan-chunk K`` selects the device-resident scanned engine (K-round
``lax.scan`` chunks, one host sync per chunk) for algorithms with the
``device_round`` capability; ``--kernel-backend`` picks the compression
pipeline's kernel implementation (jnp / pallas_interpret / pallas) on both
execution paths. ``--codec-up`` / ``--codec-down`` select the per-direction
compression codec by registry name (``repro.compression.codecs``) for every
algorithm — e.g. ``--codec-up lattice_packed --bits 4`` halves the uplink
wire bytes, ``--codec-up scalar`` runs the FedPAQ-style baseline.

Example (the (b) end-to-end driver — ~100M-param model, a few hundred
rounds; on the spmd path the client count IS the mesh data axis, so grow
--mesh-data on a pod to grow the cohort):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 200 --batch 8 --seq 128 --mesh-data 1 --log-every 20
Registry path, scanned:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --algo quafl --steps 40 --batch 4 --seq 64 --n-slots 4 --scan-chunk 10
"""
from __future__ import annotations

import argparse
from functools import partial

import jax
from repro.checkpoint import save_checkpoint
from repro.configs import get_config, get_reduced
from repro.configs.base import FedConfig
from repro.data.synthetic import federated_token_task, lm_token_stream
from repro.models.model import lm_loss
from repro.utils.cache import enable_compile_cache


def run_registry(args, cfg, fed, key):
    """Train via the unified algorithm API: registry + simulate()."""
    from repro.fed import make_algorithm, simulate
    from repro.models.model import init_lm

    k_init, k_run = jax.random.split(key)
    params0, _ = init_lm(cfg, k_init)
    loss_fn = partial(lm_loss, cfg)
    # per-client token pool: every algorithm (spmd included) samples its
    # minibatches with replacement from these rows, so the pool must be
    # large enough that a multi-hundred-round run isn't memorizing a
    # handful of sequences (the pre-refactor spmd loop generated unbounded
    # fresh streams; --pool restores arbitrarily large pools)
    pool = args.pool or max(256, max(4, args.local_steps) * args.batch)
    n_clients = fed.n_clients

    extra = {}
    if args.algo in ("fedbuff", "fedbuff_device"):
        extra = {"buffer_size": max(2, args.n_slots)}
    elif args.algo == "spmd":
        import dataclasses

        from repro.utils.compat import make_mesh
        mesh = make_mesh((args.mesh_data, args.mesh_model),
                         ("data", "model"))
        extra = {"cfg": cfg, "mesh": mesh, "batch": args.batch,
                 "seq": args.seq, "remat": False}
        # spmd maps ONE client per mesh data slice: the client count is
        # --mesh-data, not --n-slots — reconcile fed and the token task
        # loudly rather than training a silently different cohort
        if args.n_slots != args.mesh_data:
            print(f"[train] --algo spmd: client count comes from "
                  f"--mesh-data ({args.mesh_data}), overriding "
                  f"--n-slots {args.n_slots}", flush=True)
        n_clients = args.mesh_data
        fed = dataclasses.replace(fed, n_clients=n_clients, s=n_clients)

    data, batch_fn = federated_token_task(args.seed, n_clients, pool,
                                          args.batch, args.seq,
                                          cfg.vocab_size)
    alg = make_algorithm(args.algo, fed, loss_fn=loss_fn, template=params0,
                         batch_fn=batch_fn, **extra)
    eval_toks = lm_token_stream(jax.random.PRNGKey(999), args.batch,
                                args.seq, cfg.vocab_size, client_id=0)

    def eval_fn(params):
        loss, _ = lm_loss(cfg, params, {"tokens": eval_toks})
        return {"server_loss": float(loss)}

    def on_row(row):
        print(f"round {row['round']:5d} server_loss="
              f"{row.get('server_loss', float('nan')):.4f} "
              f"sim_t={row['sim_time']:.0f} "
              f"h_mean={row['h_steps_mean']:.2f} "
              f"qerr={row['quant_err']:.3e} "
              f"bits_up={row['bits_up_total']:.3g} "
              f"bits_down={row['bits_down_total']:.3g}"
              f" ({row['wall_time_s']:.1f}s)", flush=True)

    trace = simulate(alg, params0, data, k_run, rounds=args.steps,
                     eval_every=args.log_every, eval_fn=eval_fn,
                     on_row=on_row, scan_chunk=args.scan_chunk)
    print(f"engine={trace.engine} us_per_round={trace.us_per_round:.0f}")
    if args.checkpoint_dir:
        save_checkpoint(args.checkpoint_dir, trace.rounds,
                        alg.eval_params(trace.final_state),
                        extra={"arch": cfg.name, "algo": args.algo})
        print(f"checkpoint saved to {args.checkpoint_dir}")
    return trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--algo", default="spmd",
                    help="any registry name: spmd|quafl|fedavg|fedbuff|"
                         "fedbuff_device|sequential|quafl_scaffold|"
                         "adaptive_quafl ('spmd' = mesh-sharded train step "
                         "behind the same protocol)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-slots", type=int, default=2)
    ap.add_argument("--n-clients", type=int, default=0,
                    help="population size n (0 = --n-slots). The per-round "
                         "cohort stays --n-slots; the population engine "
                         "(repro.fed.population) keeps the other n-s "
                         "clients' state as store rows, so large n costs "
                         "memory, not per-round time")
    ap.add_argument("--participation", default="",
                    help="participation spec: uniform|"
                         "gamma_straggler[:strength=a]|"
                         "cyclic:period=P,phase_groups=G "
                         "(empty = FedConfig default, uniform)")
    ap.add_argument("--pool", type=int, default=0,
                    help="token-pool rows per client (0 = auto: at least "
                         "256; all algorithms sample minibatches from "
                         "this finite pool)")
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--codec-up", default="",
                    help="uplink codec spec (repro.compression.codecs "
                         "registry: lattice|lattice_packed|topk_ef|scalar|"
                         "identity, with name:key=val params, e.g. "
                         "'lattice_packed:bits=4'); empty = the "
                         "algorithm's default at --bits")
    ap.add_argument("--codec-down", default="",
                    help="downlink codec spec (same registry / syntax as "
                         "--codec-up)")
    ap.add_argument("--transport", default="dequant_psum",
                    help="mesh aggregation: dequant_psum|code_allgather|"
                         "shard_local|shard_local_codes|shard_local_rs "
                         "(the shard_local* family runs the shard_map "
                         "exchange with the psum / packed-code all-gather "
                         "/ reduce-scatter transport)")
    ap.add_argument("--kernel-backend", default="jnp",
                    choices=["jnp", "pallas_interpret", "pallas"],
                    help="compression-pipeline kernel implementation, "
                         "threaded through both the registry and spmd paths")
    ap.add_argument("--scan-chunk", default="0",
                    help=">=2 runs device_round-capable algorithms in "
                         "K-round scanned chunks (one host sync per "
                         "chunk); 'auto' picks K from a timed probe "
                         "(RoundEngine.autotune)")
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    args.scan_chunk = (args.scan_chunk if args.scan_chunk == "auto"
                       else int(args.scan_chunk))
    n_clients = args.n_clients or args.n_slots
    if n_clients < args.n_slots:
        raise SystemExit(f"--n-clients {n_clients} < --n-slots "
                         f"{args.n_slots}: cannot sample more clients per "
                         f"round than the population holds")
    fed = FedConfig(n_clients=n_clients, s=args.n_slots,
                    local_steps=args.local_steps, lr=args.lr,
                    bits=args.bits,
                    codec_up=args.codec_up, codec_down=args.codec_down,
                    transport=args.transport,
                    participation=args.participation,
                    kernel_backend=args.kernel_backend)
    key = jax.random.PRNGKey(args.seed)
    run_registry(args, cfg, fed, key)


if __name__ == "__main__":
    main()
