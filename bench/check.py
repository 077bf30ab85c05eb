"""The comparison that decides ``correct``.

After the window the plain reference of the configuration's family runs
once, teacher-forced, over each compared request's prompt and the tokens
the system served for it.  Two numbers are compared, each with its limit
from the configuration file:

- ``token_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best at that position, over the row's
  largest reference logit.  A greedy server that computes what the
  reference computes serves the best token, so the gap is 0 up to ties.
- ``logit_err``: the largest ``|logit - reference|`` over the row's
  largest reference logit, at the columns the harness kept of every
  logits row that produced a token or ended a prompt chunk.

The control is the same reference at the precision one step below the
configuration's (``control_precision``), put where the program was: its
``token_gap`` is read for the token it puts first at each compared
position, its ``logit_err`` at the same columns.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.precision import einsum

ROW_CHUNK = 256


def _rows_fn(precision):
    @jax.jit
    def rows(hrows, head, tok, alt, cols):
        lg = einsum("rd,dv->rv", hrows, head, precision)
        at = lambda i: jnp.take_along_axis(lg, i[:, None], -1)[:, 0]
        return {"max": lg.max(-1), "absmax": jnp.abs(lg).max(-1),
                "argmax": jnp.argmax(lg, -1), "at_tok": at(tok),
                "at_alt": at(alt), "cols": lg[:, cols]}
    return rows


def _gather(model, w, sizes, seqs, rows_of, precision, batch, bucket):
    """Final hidden states at the wanted rows of every sequence:
    ``(R, d)`` on the device, in the order of ``rows_of``."""
    fwd = jax.jit(lambda w, t: model.hidden(w, sizes, t, precision))
    out = []
    for i in range(0, len(seqs), batch):
        group = seqs[i:i + batch]
        toks = np.zeros((batch, bucket), np.int32)
        for j, full in enumerate(group):
            toks[j, :len(full)] = full
        h = fwd(w, jnp.asarray(toks))
        bi = np.concatenate([np.full(len(rows_of[i + j]), j)
                             for j in range(len(group))]).astype(np.int32)
        pi = np.concatenate([rows_of[i + j] for j in range(len(group))]
                            ).astype(np.int32)
        out.append(h[jnp.asarray(bi), jnp.asarray(pi)])
    return jnp.concatenate(out)


def _row_stats(hrows, head, tok, alt, cols, precision):
    fn = _rows_fn(precision)
    n = hrows.shape[0]
    pad = (-n) % ROW_CHUNK
    hp = jnp.pad(hrows, ((0, pad), (0, 0)))
    tp, ap = (jnp.asarray(np.pad(x, (0, pad)).astype(np.int32))
              for x in (tok, alt))
    parts = [fn(hp[i:i + ROW_CHUNK], head, tp[i:i + ROW_CHUNK],
                ap[i:i + ROW_CHUNK], jnp.asarray(cols))
             for i in range(0, n + pad, ROW_CHUNK)]
    return {k: np.concatenate([np.asarray(p[k]) for p in parts])[:n]
            for k in parts[0]}


def compare(model, sizes: dict, weights: dict, served: List[tuple],
            captures: List[tuple], cols: np.ndarray, *, precision: str,
            control_precision: str = "", batch: int = 4,
            bucket: int = 1024) -> Dict[str, float]:
    """The compared numbers for ``served`` (``(rid, prompt, output)``)
    and the captured logits ``(rid, position, values at cols)``; with
    ``control_precision`` also the control's, as ``control_*``."""
    caps: Dict[int, Dict[int, np.ndarray]] = {}
    for rid, pos, vals in captures:
        caps.setdefault(rid, {})[pos] = vals
    seqs, rows_of, tok_rows, cap_rows = [], [], [], []
    for rid, prompt, output in served:
        full = list(prompt) + list(output)
        first = len(prompt) - 1
        want = {first + j: t for j, t in enumerate(output)}
        have = {p: v for p, v in caps.get(rid, {}).items()
                if p < len(full) - 1 or p in want}
        rows = sorted(set(want) | set(have))
        seqs.append(full[:-1] if len(full) > 1 else full)
        rows_of.append(np.asarray(rows))
        tok_rows += [want.get(p, -1) for p in rows]
        cap_rows += [have.get(p) for p in rows]
    tok = np.asarray(tok_rows)
    head = model.head(weights)

    def stats(prec, alt):
        h = _gather(model, weights, sizes, seqs, rows_of, prec, batch,
                    bucket)
        return _row_stats(h, head, np.maximum(tok, 0), alt, cols, prec)

    # the control first: the reference row then also gives the logit of
    # the token the control puts first, from the same computation
    lo = stats(control_precision, np.zeros(len(tok), np.int64)) \
        if control_precision else None
    ref = stats(precision, lo["argmax"] if lo is not None
                else np.zeros(len(tok), np.int64))
    scale = np.maximum(ref["absmax"].astype(np.float64), 1e-30)
    served_rows = tok >= 0
    gap = (ref["max"] - ref["at_tok"]) / scale
    out = {"token_gap": float(gap[served_rows].max(initial=0.0)),
           "logit_err": _err(cap_rows, ref["cols"], scale),
           "rows": int(len(tok)), "tokens": int(served_rows.sum()),
           "requests": len(served)}
    if lo is not None:
        out["control_token_gap"] = float(
            ((ref["max"] - ref["at_alt"]) / scale).max())
        out["control_logit_err"] = _err(
            [lo["cols"][i] if c is not None else None
             for i, c in enumerate(cap_rows)], ref["cols"], scale)
    return out


def _err(values, ref_cols, scale) -> float:
    worst = 0.0
    for i, v in enumerate(values):
        if v is not None:
            d = np.abs(np.asarray(v, np.float64) - ref_cols[i]).max()
            worst = max(worst, float(d / scale[i]))
    return worst


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            prefix: str = "") -> Dict[str, dict]:
    """Each compared number beside its limit."""
    return {k: {"value": numbers[prefix + k], "limit": limits[k]}
            for k in limits}


def passes(checks: Dict[str, dict]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
