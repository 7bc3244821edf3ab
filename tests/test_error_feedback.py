"""Model-tracking ablation (paper §2.2): a server tracks a drifting model
x_t from quantized client messages.

 * lattice — position-aware: client sends Enc(x_t), server decodes against
   its own estimate; NO client memory.
 * qsgd-delta — client sends Q(x_t − x̂_{t−1}); unbiased but error compounds.
 * qsgd + error feedback — needs a d-sized client accumulator.

The paper's claim: lattice matches EF's tracking quality without the memory.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compression import LatticeCodec, ScalarCodec
from repro.compression.error_feedback import ErrorFeedbackQSGD


def _drift(key, d, steps, scale=0.05):
    xs = [jax.random.normal(key, (d,))]
    for i in range(steps):
        xs.append(xs[-1] + scale * jax.random.normal(
            jax.random.fold_in(key, i), (d,)))
    return xs


def run_tracking(d=4096, steps=30, bits=6, seed=0):
    key = jax.random.PRNGKey(seed)
    xs = _drift(key, d, steps)
    lat = LatticeCodec(bits=bits)
    qsg = ScalarCodec(bits=bits)
    ef = ErrorFeedbackQSGD(bits=bits)

    est_lat = xs[0]
    est_del = xs[0]
    est_ef = xs[0]
    st = ef.init(d)
    errs = {"lattice": [], "qsgd_delta": [], "qsgd_ef": []}
    for t in range(1, steps + 1):
        k = jax.random.fold_in(key, 1000 + t)
        x = xs[t]
        # lattice: encode x, decode vs server estimate (no client state)
        msg = lat.encode(k, x, jnp.linalg.norm(x - est_lat) + 1e-8)
        est_lat = lat.decode(k, msg, est_lat)
        # qsgd on the delta
        est_del = est_del + qsg.decode(k, qsg.encode(k, x - est_del))
        # qsgd + EF
        _, dec, st = ef.compress(k, x - est_ef, st)
        est_ef = est_ef + dec
        nx = float(jnp.linalg.norm(x))
        errs["lattice"].append(float(jnp.linalg.norm(est_lat - x)) / nx)
        errs["qsgd_delta"].append(float(jnp.linalg.norm(est_del - x)) / nx)
        errs["qsgd_ef"].append(float(jnp.linalg.norm(est_ef - x)) / nx)
    return {k: float(np.mean(v[-10:])) for k, v in errs.items()}


def test_lattice_tracks_without_memory():
    errs = run_tracking()
    # every scheme must actually track (no divergence)
    assert errs["qsgd_ef"] < 0.5, errs
    # lattice stays accurate and is competitive with EF (which needs a
    # d-sized client accumulator); both beat plain delta-QSGD or tie
    assert errs["lattice"] < 0.05, errs
    assert errs["lattice"] < 1.5 * errs["qsgd_ef"], errs
    assert errs["lattice"] <= errs["qsgd_delta"] * 1.5, errs


def test_ef_accumulator_is_the_memory_cost():
    ef = ErrorFeedbackQSGD(bits=8)
    st = ef.init(1000)
    assert st.error.shape == (1000,)  # the client memory the paper avoids
