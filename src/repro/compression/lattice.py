"""Position-aware lattice quantizer (Davies et al. [7], Lemma 3.1).

Semantics (paper §2.2): ``Enc(x)`` maps x to b-bit codes; ``Dec(y, Enc(x))``
recovers Q(x) using any reference y with ``‖x − y‖`` small. Practical
construction: randomized Hadamard rotation + *modulo* uniform quantization —
the codes are the stochastically-rounded rotated coordinates mod 2^b, and the
decoder snaps to the representative nearest its own rotated reference. The
three Lemma 3.1 properties hold whenever the wrap condition is met:

  1. unbiased decoding   E[Q(x)] = x      (stochastic rounding)
  2. error bound         ‖Q(x) − x‖ ≤ γ·sqrt(d_pad)        (ℓ∞ ≤ γ)
  3. bit cost            d·b + O(1) bits; b ~ log(‖x−y‖/γ)

γ is chosen from a *distance hint* the encoder always has locally (the client
knows ‖Y − X^i‖ = η·η_i·‖h̃‖; the server uses its previous round delta), so
the error is proportional to the model *distance*, never the model norm —
this is exactly what makes direct QSGD-style quantization unsound here
(paper §2.2 'Fully-Quantized Communication').

The encode/decode math itself lives in the compression *pipeline* backend
registry (repro.compression.pipeline): ``backend="jnp"`` composes pure-jnp
ops, ``"pallas_interpret"``/``"pallas"`` run the fused Pallas kernels
(rotate+round+wrap in one pass; rotate-ref+snap+inverse-rotate in one pass).
The quantizer is a thin per-message wrapper that fixes the wire format
(``LatticeMsg``) and the key schedule (split -> rotation key, rounding key).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.compression.rotation import DEFAULT_BLOCK, _signs, dither, pad_len
from repro.compression.pipeline import (GAMMA_NORM_FLOOR, coord_bound,
                                        get_backend, wrap_gamma)


class LatticeMsg(NamedTuple):
    codes: jnp.ndarray     # (d_pad,) unsigned ints in [0, 2^b)
    gamma: jnp.ndarray     # () fp32 — transmitted scale (O(1) overhead)


@dataclass(frozen=True)
class LatticeQuantizer:
    bits: int = 8
    block: int = DEFAULT_BLOCK
    safety: float = 8.0    # head-room factor on the wrap window
    backend: str = "jnp"   # pipeline backend running the actual math

    @property
    def levels(self) -> int:
        return 1 << self.bits

    def code_dtype(self):
        if self.bits <= 8:
            return jnp.uint8
        if self.bits <= 16:
            return jnp.uint16
        return jnp.uint32

    def _ops(self):
        return get_backend(self.backend)

    # -- γ from the encoder-local distance hint ----------------------------
    def gamma_for(self, dist_hint: jnp.ndarray, d: int) -> jnp.ndarray:
        """dist_hint: upper estimate of ‖x − ref‖₂. After rotation the
        difference coordinates are subgaussian with scale dist/sqrt(d); the
        wrap window 2^b·γ must exceed twice the max coordinate."""
        return wrap_gamma(dist_hint, d, bits=self.bits, block=self.block,
                          safety=self.safety)

    # -- Enc ----------------------------------------------------------------
    def encode(self, key, x: jnp.ndarray, dist_hint) -> LatticeMsg:
        """x: flat (d,) fp32. key: shared rotation+rounding key for the
        interaction (the server's round seed — both ends derive it)."""
        d = x.shape[0]
        d_pad = pad_len(d, self.block)
        gamma = self.gamma_for(jnp.asarray(dist_hint, jnp.float32), d)
        # fp32 precision floor: the modulo decode needs y/γ (and w/γ) to
        # keep sub-integer precision, so γ ≥ max|rot(x)|·2^-18. The max
        # rotated coordinate is estimated pre-rotation from the (rotation-
        # invariant) norm so γ is available before the fused rotate+quantize
        # kernel runs. When the distance hint is tiny relative to the model
        # norm the error bound degrades to the model's own fp32 resolution
        # instead of silently mis-decoding.
        gamma = jnp.maximum(gamma, coord_bound(jnp.linalg.norm(x), d_pad)
                            * GAMMA_NORM_FLOOR)
        krot, krnd = jax.random.split(key)
        signs = _signs(krot, d_pad)
        u = dither(krnd, (d_pad,))
        x2 = jnp.pad(x.astype(jnp.float32), (0, d_pad - d))[None]
        codes = self._ops().encode(x2, signs, u[None], gamma[None],
                                   bits=self.bits, block=self.block,
                                   want_rotated=False)[0]
        return LatticeMsg(codes=codes.astype(self.code_dtype()), gamma=gamma)

    # -- Dec(ref, msg) -------------------------------------------------------
    def decode(self, key, msg: LatticeMsg, ref: jnp.ndarray) -> jnp.ndarray:
        """ref: flat (d,) decoding key (paper's y). Returns Q(x) of len d.

        One fused pass: rotate the reference, snap each code to the
        representative nearest the reference coordinate, inverse-rotate."""
        d = ref.shape[0]
        d_pad = pad_len(d, self.block)
        krot, _ = jax.random.split(key)
        signs = _signs(krot, d_pad)
        ref2 = jnp.pad(ref.astype(jnp.float32), (0, d_pad - d))[None]
        x = self._ops().decode(msg.codes[None], ref2, signs,
                               jnp.reshape(msg.gamma, (1,)), bits=self.bits,
                               block=self.block)[0]
        return x[:d]

    # -- exact bit accounting (Lemma 3.8) ------------------------------------
    def message_bits(self, d: int) -> int:
        return pad_len(d, self.block) * self.bits + 32  # + γ scalar


@dataclass(frozen=True)
class QSGDQuantizer:
    """Standard norm-scaled stochastic quantizer [Alistarh et al., 1]. Not
    position-aware: error ∝ ‖x‖ (used as the paper's Figure-5 baseline)."""
    bits: int = 8
    block: int = DEFAULT_BLOCK  # unused; uniform API

    @property
    def levels(self) -> int:
        return (1 << (self.bits - 1)) - 1  # signed levels

    def encode(self, key, x: jnp.ndarray, dist_hint=None):
        norm = jnp.linalg.norm(x) + 1e-12
        y = jnp.abs(x) / norm * self.levels
        u = dither(key, x.shape)
        q = jnp.floor(y + u) * jnp.sign(x)
        return LatticeMsg(codes=q.astype(jnp.int32), gamma=norm)

    def decode(self, key, msg: LatticeMsg, ref=None):
        return msg.codes.astype(jnp.float32) * (msg.gamma / self.levels)

    def message_bits(self, d: int) -> int:
        return d * self.bits + 32


@dataclass(frozen=True)
class IdentityQuantizer:
    bits: int = 32

    def encode(self, key, x, dist_hint=None):
        return LatticeMsg(codes=x, gamma=jnp.float32(1.0))

    def decode(self, key, msg, ref=None):
        return msg.codes

    def message_bits(self, d: int) -> int:
        return d * 32


def make_quantizer(name: str, bits: int, backend: str = "jnp"):
    if name == "lattice":
        return LatticeQuantizer(bits=bits, backend=backend)
    if name == "qsgd":
        return QSGDQuantizer(bits=bits)
    if name == "none":
        return IdentityQuantizer()
    raise ValueError(name)
