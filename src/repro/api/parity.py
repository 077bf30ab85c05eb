"""Logit parity of a compiled Program against the plain f32 reference.

The reference is the ``jax`` backend (the model oracle) run under
``jax.default_matmul_precision("highest")``: every contraction in f32.
The same run at ``"default"`` precision (one bf16 MXU pass on a TPU) is
also measured, so a caller can show the tolerance tells the two apart.
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np

__all__ = ["PARITY_TOL", "relative_error", "decode_parity"]

#: Largest ``max |logits - reference| / max |reference|`` an f32 program
#: may show against the f32 reference.  Reordered f32 sums (tiles, chunked
#: softmax) move logits by ~1e-6 of their scale; rounding the matmul
#: operands to bf16 moves them by ~1e-3, which this bound rejects.
PARITY_TOL = 1e-4


def relative_error(got: np.ndarray, want: np.ndarray) -> float:
    """``max |got - want|`` over ``max |want|``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def decode_parity(program, reference, tokens, seq_lens) -> Dict[str, float]:
    """Decode the teacher-forced ``tokens`` ``(steps, batch)`` through
    ``program`` from its current state, then through the ``jax``-backend
    ``reference`` from that same state, once per matmul precision.

    Returns the worst per-step :func:`relative_error` of the program's
    logits against the reference at ``"highest"`` and at ``"default"``
    precision.  ``program`` advances by ``len(tokens)`` steps."""
    assert reference.backend == "jax", reference.backend
    state = program.get_state()
    seq_lens = np.asarray(seq_lens, np.int32)
    got = [program.step(t, seq_lens + i) for i, t in enumerate(tokens)]
    errs = {}
    for precision in ("highest", "default"):
        reference.set_state(state)
        with jax.default_matmul_precision(precision):
            errs[precision] = max(
                relative_error(g, reference.step(t, seq_lens + i))
                for i, (t, g) in enumerate(zip(tokens, got)))
    return errs
