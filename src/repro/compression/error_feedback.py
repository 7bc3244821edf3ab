"""Error-feedback (EF14/EF21-style) compression — the alternative the paper
REJECTS (§2.2): transmitting quantized updates with a client-side error
accumulator needs extra memory at the client and second-moment assumptions;
the position-aware lattice quantizer needs neither. Implemented so the
trade-off is runnable (see the tracking ablation in
tests/test_error_feedback.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Tuple

import jax.numpy as jnp

from repro.compression.codecs import LatticeMsg, ScalarCodec


class EFState(NamedTuple):
    error: jnp.ndarray     # client-side residual memory (d,)


@dataclass(frozen=True)
class ErrorFeedbackQSGD:
    """QSGD on (delta + carried error); the un-transmitted residual is
    remembered and re-injected next round."""
    bits: int = 8

    def init(self, d: int) -> EFState:
        return EFState(error=jnp.zeros((d,), jnp.float32))

    def compress(self, key, delta: jnp.ndarray,
                 state: EFState) -> Tuple[LatticeMsg, jnp.ndarray, EFState]:
        """Returns (message, decoded value at the server, new client state).

        QSGD is not a contraction for small bits / large d (variance bound
        ω = √d/levels can exceed 1), so the decoded value is scaled by the
        standard 1/(1+ω) to keep the EF recursion stable."""
        import numpy as np
        q = ScalarCodec(bits=self.bits)
        target = delta + state.error
        msg = q.encode(key, target)
        omega = np.sqrt(delta.shape[0]) / q.levels
        decoded = q.decode(key, msg) / (1.0 + omega)
        return msg, decoded, EFState(error=target - decoded)

    def message_bits(self, d: int) -> int:
        return ScalarCodec(bits=self.bits).message_bits(d)
