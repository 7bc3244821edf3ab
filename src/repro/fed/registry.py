"""String registry of federated server algorithms.

``make_algorithm(name, fed, loss_fn=..., template=..., batch_fn=...)``
constructs any server variant in the repo behind the ONE
:class:`repro.fed.FedAlgorithm` protocol, so drivers select algorithms by
name (``launch/train.py --algo``, benchmark sweeps, the ``compare()``
harness) instead of hand-wiring a class per experiment:

  ``quafl``           paper Alg. 1 (async polling + lattice-quantized
                      exchange); kwargs: ``avg_mode``, ``uniform_speeds``,
                      ``exchange_impl``
  ``fedavg``          synchronous FedAvg (waits for stragglers,
                      uncompressed); kwargs: ``uniform_speeds``
  ``fedbuff``         buffered asynchronous aggregation; kwargs:
                      ``buffer_size``, ``server_lr``, ``uniform_speeds``
  ``sequential``      single slow node, one step per round (paper Fig. 3)
  ``quafl_scaffold``  QuAFL + SCAFFOLD control variates (beyond-paper);
                      QuAFL kwargs
  ``adaptive_quafl``  QuAFL under the adaptive bit-width controller
                      (beyond-paper); kwargs: ``lo``, ``hi``, ``b_min``,
                      ``b_max``
  ``fedbuff_device``  FedBuff with its event state as a pure pytree
                      (device ring buffer, jit/scan-able rounds); FedBuff
                      kwargs plus ``completion_table`` (seed bridge)
  ``spmd``            the mesh-sharded QuAFL train step behind the
                      protocol (one client per mesh data slice); kwargs:
                      ``cfg`` (ModelConfig, REQUIRED), ``mesh``, ``batch``,
                      ``seq``, ``fed_mode``, ``transport``, ``remat``
  ``compressed_fedavg``  FedPAQ-family compressed synchronous FedAvg
                      (arXiv:2106.07155, arXiv:2308.08165) built purely
                      from the codec API; kwargs: ``server_lr``,
                      ``uniform_speeds``

Every algorithm that communicates additionally accepts ``uplink=`` /
``downlink=`` codec specs (:mod:`repro.compression.codecs` — names like
``"lattice_packed"``, ``"scalar:bits=4"``, or a ``{"fast": ..., "slow":
...}`` per-client group map), defaulting to ``FedConfig.codec_up`` /
``codec_down`` and then to the algorithm's own default (``lattice`` for
the QuAFL family, ``spmd`` and ``compressed_fedavg``'s uplink, ``identity``
elsewhere); the metrics' ``bits_up`` / ``bits_down`` are computed by the
selected codecs.

The round-sampling algorithms (``quafl``, ``fedavg``, ``quafl_scaffold``,
``adaptive_quafl``, ``compressed_fedavg``) also accept ``participation=``
— a :mod:`repro.fed.population` spec (``"uniform"``,
``"gamma_straggler:strength=2"``, ``"cyclic:period=8,phase_groups=4"``, or
a ``Participation`` instance) selecting WHO answers each round's poll,
defaulting to ``FedConfig.participation`` and then uniform — and
``client_mesh=`` to shard the per-client population store over a
client-parallel mesh axis. ``fedbuff``/``fedbuff_device`` are event-driven
(every client completion arrives; there is no per-round draw to re-spec),
and ``sequential``/``spmd`` have no sampled cohort.

The registry is extensible: third-party variants join via
:func:`register_algorithm` and immediately work with ``simulate()`` /
``compare()`` and every registry-driven entry point.

Core modules are imported lazily inside the builders — ``repro.core``
imports ``repro.fed.clock``, so eager imports here would be circular.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

from repro.configs.base import FedConfig
from repro.fed.api import FedAlgorithm


def _build_quafl(fed, loss_fn, template, batch_fn, **kw):
    from repro.core.quafl import QuAFL
    return QuAFL(fed=fed, loss_fn=loss_fn, template=template,
                 batch_fn=batch_fn, **kw)


def _build_fedavg(fed, loss_fn, template, batch_fn, **kw):
    from repro.core.fedavg import FedAvg
    return FedAvg(fed=fed, loss_fn=loss_fn, template=template,
                  batch_fn=batch_fn, **kw)


def _build_fedbuff(fed, loss_fn, template, batch_fn, **kw):
    from repro.core.fedbuff import FedBuff
    return FedBuff(fed=fed, loss_fn=loss_fn, template=template,
                   batch_fn=batch_fn, **kw)


def _build_sequential(fed, loss_fn, template, batch_fn, **kw):
    from repro.core.baseline import Sequential
    return Sequential(fed=fed, loss_fn=loss_fn, template=template,
                      batch_fn=batch_fn, **kw)


def _build_scaffold(fed, loss_fn, template, batch_fn, **kw):
    from repro.core.extensions import QuaflScaffold
    return QuaflScaffold(fed=fed, loss_fn=loss_fn, template=template,
                         batch_fn=batch_fn, **kw)


def _build_adaptive(fed, loss_fn, template, batch_fn, **kw):
    from repro.core.extensions import AdaptiveQuaflAlgorithm
    from repro.core.quafl import QuAFL
    quafl_kw = {k: kw.pop(k) for k in ("avg_mode", "uniform_speeds",
                                       "exchange_impl", "uplink", "downlink",
                                       "participation", "client_mesh")
                if k in kw}

    def make_alg(f):
        return QuAFL(fed=f, loss_fn=loss_fn, template=template,
                     batch_fn=batch_fn, **quafl_kw)

    return AdaptiveQuaflAlgorithm(fed, make_alg, **kw)


def _build_fedbuff_device(fed, loss_fn, template, batch_fn, **kw):
    from repro.core.fedbuff import FedBuffDevice
    return FedBuffDevice(fed=fed, loss_fn=loss_fn, template=template,
                         batch_fn=batch_fn, **kw)


def _build_compressed_fedavg(fed, loss_fn, template, batch_fn, **kw):
    from repro.core.fedavg import CompressedFedAvg
    return CompressedFedAvg(fed=fed, loss_fn=loss_fn, template=template,
                            batch_fn=batch_fn, **kw)


def _build_spmd(fed, loss_fn, template, batch_fn, **kw):
    # loss_fn/batch_fn are protocol-uniform arguments the mesh path does not
    # consume: the train step hardwires the LM loss and samples minibatches
    # from the token-pool `data` itself.
    from repro.launch.spmd import SpmdAlgorithm
    return SpmdAlgorithm(fed=fed, template=template, **kw)


_BUILDERS: Dict[str, Callable[..., FedAlgorithm]] = {
    "quafl": _build_quafl,
    "fedavg": _build_fedavg,
    "fedbuff": _build_fedbuff,
    "sequential": _build_sequential,
    "quafl_scaffold": _build_scaffold,
    "adaptive_quafl": _build_adaptive,
    "fedbuff_device": _build_fedbuff_device,
    "spmd": _build_spmd,
    "compressed_fedavg": _build_compressed_fedavg,
}


def registered_algorithms() -> Tuple[str, ...]:
    """Names accepted by :func:`make_algorithm`, in registration order."""
    return tuple(_BUILDERS)


def register_algorithm(name: str,
                       builder: Callable[..., FedAlgorithm]) -> None:
    """Register a custom server variant. ``builder`` receives
    ``(fed, loss_fn, template, batch_fn, **kwargs)`` and must return a
    :class:`repro.fed.FedAlgorithm`."""
    if name in _BUILDERS:
        raise ValueError(f"algorithm {name!r} already registered")
    _BUILDERS[name] = builder


def make_algorithm(name: str, fed: FedConfig, *, loss_fn, template,
                   batch_fn, **kwargs) -> FedAlgorithm:
    """Build the named server algorithm behind the unified protocol.

    ``loss_fn(params_pytree, batch) -> (loss, aux)``; ``template`` is the
    params pytree the flat optimization vectors unflatten against;
    ``batch_fn(client_data, key) -> batch`` samples one client minibatch.
    Algorithm-specific ``kwargs`` are forwarded (see module docstring).
    """
    if name not in _BUILDERS:
        raise ValueError(f"unknown algorithm {name!r}; choose from "
                         f"{sorted(_BUILDERS)}")
    return _BUILDERS[name](fed, loss_fn, template, batch_fn, **kwargs)
