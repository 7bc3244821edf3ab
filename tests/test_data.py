"""Federated data pipeline properties."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.data import (gaussian_mixture, lm_token_stream,
                        make_federated_classification, partition_by_class,
                        partition_iid)


@settings(deadline=None, max_examples=10)
@given(n_clients=st.integers(2, 16), seed=st.integers(0, 100))
def test_partition_iid_disjoint_cover(n_clients, seed):
    key = jax.random.PRNGKey(seed)
    data = gaussian_mixture(key, 64 * n_clients, d=8, n_classes=4)
    part = partition_iid(key, data, n_clients)
    assert part["x"].shape[0] == n_clients
    # flattened sample set sizes add up and rows are unique
    xs = np.asarray(part["x"]).reshape(-1, 8)
    assert len(np.unique(xs.round(5), axis=0)) == xs.shape[0]


def test_partition_by_class_label_skew():
    """Non-iid split: each client sees a strict subset of classes."""
    key = jax.random.PRNGKey(0)
    data = gaussian_mixture(key, 4000, d=8, n_classes=10)
    part = partition_by_class(key, data, 10, 10)
    for i in range(10):
        labels = np.unique(np.asarray(part["y"][i]))
        assert len(labels) <= 3  # heavy concentration vs 10 classes


def test_label_distributions_differ_vs_iid():
    key = jax.random.PRNGKey(1)
    data = gaussian_mixture(key, 2000, d=8, n_classes=10)
    iid = partition_iid(key, data, 8)
    non = partition_by_class(key, data, 8, 10)

    def spread(part):
        hists = [np.bincount(np.asarray(part["y"][i]), minlength=10)
                 for i in range(8)]
        hists = np.stack(hists) / np.maximum(
            np.stack(hists).sum(1, keepdims=True), 1)
        return float(np.std(hists, axis=0).mean())

    assert spread(non) > 3 * spread(iid)


def test_lm_token_stream_ranges_and_noniid():
    key = jax.random.PRNGKey(2)
    a = lm_token_stream(key, 4, 64, 1000, client_id=0)
    b = lm_token_stream(key, 4, 64, 1000, client_id=1)
    assert a.shape == (4, 64)
    assert int(a.min()) >= 0 and int(a.max()) < 1000
    # different clients see permuted marginals
    assert not bool(jnp.all(a == b))


def test_make_federated_classification_shapes():
    part, test = make_federated_classification(0, 6, samples_per_client=32,
                                               d=8, n_classes=4)
    assert part["x"].shape == (6, 32, 8)
    assert test["x"].shape[0] == 1024
