"""Quickstart: the unified federated-algorithm API on a federated
classification task.

Every server variant in the repo — QuAFL (paper Alg. 1), FedAvg, FedBuff,
sequential, and the beyond-paper extensions — implements ONE protocol
(``init / round / eval_params``), so the paper's headline comparison is
three calls: build algorithms by name from the registry, hand them to
``compare()`` with an equal simulated-wall-clock budget, read the traces.
16 clients (30% slow), non-iid by-class split.

COMPRESSION is composable the same way: every algorithm takes ``uplink=``
/ ``downlink=`` codec specs from the ``repro.compression.codecs`` registry
— here QuAFL runs with (a) the default 8-bit lattice codec, (b) a
PER-CLIENT heterogeneous uplink (fast clients at b=8, the slow 30% packed
at b=4), and FedPAQ-style compressed FedAvg joins as just another registry
name.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.configs.base import FedConfig
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch
from repro.fed import compare, make_algorithm
from repro.models.mlp import init_mlp_classifier, mlp_loss


def main():
    fed = FedConfig(n_clients=16, s=4, local_steps=5, lr=0.3, bits=8,
                    swt=10.0)
    part, test = make_federated_classification(0, fed.n_clients, d=32,
                                               n_classes=10, iid=False)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(0), 32, 64, 10)
    bf = lambda d, k: client_batch(k, d, 32)

    mk = lambda name, **kw: make_algorithm(name, fed, loss_fn=mlp_loss,
                                           template=params0, batch_fn=bf,
                                           **kw)
    algs = {
        "quafl": mk("quafl"),
        # heterogeneous uplink: stragglers send 4-bit codes, fast clients 8
        "quafl_het": mk("quafl", uplink={"fast": "lattice",
                                         "slow": "lattice_packed:bits=4"}),
        "fedavg": mk("fedavg"),
        # FedPAQ-style compressed FedAvg: one registry name + one codec spec
        "fedpaq": mk("compressed_fedavg", uplink="scalar"),
    }

    # equal simulated wall-clock: ~120 QuAFL rounds' worth of time. FedAvg
    # fits far fewer rounds in it — its synchronous server waits for the
    # slowest sampled client every round.
    budget = 120 * (fed.swt + fed.sit)
    traces = compare(algs, params0, part, jax.random.PRNGKey(1),
                     until_sim_time=budget, eval_every=24,
                     eval_fn=lambda p: {"acc": float(mlp_loss(p, test)[1]
                                                    ["acc"])})

    print("algorithm | rounds |  sim t |   acc | bits up | bits down")
    for name, tr in traces.items():
        f = tr.final
        print(f"{name:9s} | {tr.rounds:6d} | {f['sim_time']:6.0f} |"
              f" {f['acc']:5.3f} | {f['bits_up_total']:7.3g} |"
              f" {f['bits_down_total']:9.3g}")

    h, q = traces["quafl_het"].final, traces["quafl"].final
    print(f"\nheterogeneous uplink (slow 30% at b=4) sends "
          f"{q['bits_up_total'] / h['bits_up_total']:.2f}x fewer uplink "
          f"bits than uniform b=8 at acc {h['acc']:.3f} vs {q['acc']:.3f}")

    q, a = traces["quafl"].final, traces["fedavg"].final
    qbits = q["bits_up_total"] + q["bits_down_total"]
    abits = a["bits_up_total"] + a["bits_down_total"]
    ratio = (abits / traces["fedavg"].rounds) / (qbits / traces["quafl"].rounds)
    print(f"\nQuAFL sends {ratio:.1f}x fewer bits per round than FedAvg at "
          f"the same simulated wall-clock budget")
    print(f"QuAFL slow-client zero-progress polls (last round): "
          f"{q['h_zero_frac']:.2f} — the algorithm tolerates them (paper §4)")


if __name__ == "__main__":
    main()
