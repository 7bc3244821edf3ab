"""Regenerate tests/golden_pr3.npz — the PR 3 bit-for-bit anchor.

Runs a deterministic 3-round slice of the core registry algorithms through
the PUBLIC API (make_algorithm + round) and stores the resulting server
vectors plus the per-round bit counters. The slice was first recorded by
the PR 3 tree, BEFORE the codec/transport redesign: the redesigned default
path (``lattice`` codec both directions) must reproduce it exactly, which is
what ``tests/test_codecs.py::test_default_lattice_matches_pr3_golden`` pins.

Re-anchored under jax 0.9.0. jax >= 0.5 draws other random bits by
default (``jax_threefry_partitionable`` is on), so the file the PR 3 tree
wrote under jax 0.4.37 (kept as ``tests/golden_pr3_jax04.npz``) no longer
applies as is. Run with ``jax.threefry_partitionable(False)``, the tree
reproduces that file's bit counters exactly, and the quafl / fedavg /
fedbuff_device servers to <= 1.94e-7 (fp32 rounding of the newer XLA;
``test_default_lattice_matches_pr3_jax04_golden`` pins this). The
quafl_scaffold server differed by up to 1.79e-3. In its first round two
uplink codes have y/γ + u exactly on an integer (-26.0 at coordinate 52
of client 0's model message, -4646.0 at coordinate 802 of client 2's), so
any last-ulp rounding moves the code by one step. Pushing those two codes
to the other side of their integer, and no other, reproduces the PR 3
scaffold server to 1.19e-7.

    PYTHONPATH=src python tests/make_golden.py
"""
import jax
import numpy as np

from repro.configs.base import FedConfig
from repro.data import make_federated_classification
from repro.data.synthetic import client_batch
from repro.fed import make_algorithm
from repro.models.mlp import init_mlp_classifier, mlp_loss
from repro.utils.tree import tree_flatten_vector

GOLDEN = {
    "quafl": dict(),
    "quafl_scaffold": dict(),
    "fedavg": dict(),
    "fedbuff_device": dict(buffer_size=2, uplink="lattice"),
}


def main(path="tests/golden_pr3.npz"):
    fed = FedConfig(n_clients=6, s=3, local_steps=2, lr=0.3, bits=8)
    part, _ = make_federated_classification(0, fed.n_clients, d=16,
                                            n_classes=4)
    params0, _ = init_mlp_classifier(jax.random.PRNGKey(0), 16, 32, 4)
    bf = lambda dd, k: client_batch(k, dd, 16)
    out = {}
    for name, kw in GOLDEN.items():
        alg = make_algorithm(name, fed, loss_fn=mlp_loss, template=params0,
                             batch_fn=bf, **kw)
        state = alg.init(params0)
        key = jax.random.PRNGKey(7)
        ups, downs = [], []
        for _ in range(3):
            key, sub = jax.random.split(key)
            state, m = alg.round(state, part, sub)
            ups.append(float(m["bits_up"]))
            downs.append(float(m["bits_down"]))
        out[f"{name}/server"] = np.asarray(
            tree_flatten_vector(alg.eval_params(state)))
        out[f"{name}/bits_up"] = np.asarray(ups)
        out[f"{name}/bits_down"] = np.asarray(downs)
    np.savez(path, **out)
    print(f"wrote {len(out)} arrays to {path}")


if __name__ == "__main__":
    main()
