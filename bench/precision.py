"""Contractions at a named precision, the same on every backend.

``highest`` is f32 arithmetic (``lax.Precision.HIGHEST``).  ``high`` is
the three-pass bf16 product a TPU's ``HIGH`` makes, written out: each
operand split into a bf16 head and a bf16 tail, and the head x head,
head x tail and tail x head products summed in f32, the tail x tail one
dropped.  Writing it out makes the control the same computation on a CPU
as on a chip.  The split rounds with ``reduce_precision``, which XLA keeps
as written; a pair of casts f32 -> bf16 -> f32 it may drop as excess
precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    head = _bf16(x)
    return head, _bf16(x - head)


def einsum(eq: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=_HI)
    if precision == "high":
        ah, at = _split(a)
        bh, bt = _split(b)
        return (jnp.einsum(eq, ah, bh, precision=_HI)
                + (jnp.einsum(eq, ah, bt, precision=_HI)
                   + jnp.einsum(eq, at, bh, precision=_HI)))
    raise ValueError(f"unknown precision {precision!r}")
