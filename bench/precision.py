"""The precisions at which a reference runs: the sound one and the controls.

A mode names two things: the format of the model's matrix products and
what the exchange's rotations see of their inputs.

- ``f32``: the reference. The model at full float32 (``Precision.HIGHEST``:
  on a TPU the default would run one bfloat16 pass), the rotations too.
- ``fp8``: the control of a model that computes in bfloat16: both inputs
  of each model matmul rounded to float8 e4m3, then multiplied exactly,
  which is what a program computing in that format would see.
- ``rot_high``: the control of the exchange, whose rotations the
  configurations state in float32 at ``HIGHEST``: each rotation product's
  input rounded to the sum of two bfloat16 numbers, the model as in
  ``f32``. The Hadamard factor is exact in bfloat16, so this is what
  ``Precision.HIGH`` (three bfloat16 passes) computes, on any platform.
- ``rot_bf16``: the same with one bfloat16 number, ``Precision.DEFAULT``
  on a TPU: a step below the nearest, read in calibration as a fault of
  the exchange.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _two_bf16(x):
    hi = _bf16(x)
    return hi + _bf16(x - hi)


MODES = {"f32": (None, None),
         "fp8": (jnp.float8_e4m3fn, None),
         "rot_high": (None, _two_bf16),
         "rot_bf16": (None, _bf16)}
CONTROLS = ("fp8", "rot_high", "rot_bf16")


def einsum_at(mode: str):
    fmt = MODES[mode][0]

    def ein(spec, a, b):
        if fmt is not None:
            a = a.astype(fmt).astype(jnp.float32)
            b = b.astype(fmt).astype(jnp.float32)
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    return ein


def rotation_operand(mode: str):
    """The rounding of a rotation product's input in ``mode`` (None: none)."""
    return MODES[mode][1]
