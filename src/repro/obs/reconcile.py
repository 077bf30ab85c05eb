"""Predicted-vs-observed timeline reconciliation.

The predicted trace is in roofline seconds, the observed one in logical
ticks — absolute times are incomparable, so the report measures *shape*:

* **normalized-time skew** — each side's start times normalized by its
  own makespan to [0, 1]; skew = observed − predicted per task,
* **rank skew** — each task's position in the start-order permutation
  of each side, difference normalized by (n − 1): 0 means the kernel
  executed tasks in exactly the predicted order,
* **worker agreement** — fraction of matched tasks whose observed lane
  equals the predicted placement (a compile-time decision).

Bounded skew here is what makes ``replay_partition`` a trustworthy cost
oracle for the autotuner.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from .trace import TaskTrace

__all__ = ["ReconcileReport", "reconcile"]


@dataclasses.dataclass
class ReconcileReport:
    n_predicted: int
    n_observed: int
    matched: int
    unmatched_predicted: List[int]     # task ids only the prediction has
    unmatched_observed: List[int]      # task ids only the kernel ran
    mean_abs_skew: float               # normalized-time start skew
    max_abs_skew: float
    mean_abs_rank_skew: float          # start-order permutation skew
    max_abs_rank_skew: float
    worker_agreement: float            # matched tasks on predicted lane
    #: per-kind {n, mean_abs_skew, mean_abs_rank_skew}
    per_kind: Dict[str, Dict[str, float]]
    #: per-task normalized-time start skew (observed - predicted)
    per_task: Dict[int, float]

    def summary(self) -> str:
        lines = [
            f"reconcile: {self.matched} matched "
            f"({self.n_predicted} predicted / {self.n_observed} "
            f"observed), {len(self.unmatched_predicted)} / "
            f"{len(self.unmatched_observed)} unmatched",
            f"  start skew (normalized time): mean "
            f"{self.mean_abs_skew:.4f}  max {self.max_abs_skew:.4f}",
            f"  start skew (rank): mean {self.mean_abs_rank_skew:.4f}  "
            f"max {self.max_abs_rank_skew:.4f}",
            f"  worker agreement: {self.worker_agreement:.1%}",
        ]
        for kind, st in sorted(self.per_kind.items()):
            lines.append(
                f"  {kind:>18}: n={int(st['n']):3d}  "
                f"time {st['mean_abs_skew']:.4f}  "
                f"rank {st['mean_abs_rank_skew']:.4f}")
        return "\n".join(lines)


def _norm_starts(trace: TaskTrace) -> Dict[int, float]:
    """Compute tasks only (kind > 0): noop/dummy slots are
    synchronization artifacts, not timeline claims."""
    span = max(trace.makespan, 1e-30)
    return {e.task: e.start / span for e in trace.events
            if e.task >= 0 and e.kind > 0}


def _ranks(starts: Dict[int, float]) -> Dict[int, float]:
    order = sorted(starts, key=lambda t: (starts[t], t))
    n = max(len(order) - 1, 1)
    return {t: i / n for i, t in enumerate(order)}


def reconcile(predicted: TaskTrace, observed: TaskTrace
              ) -> ReconcileReport:
    """Match two timelines by task id and report their skew."""
    p_ev = predicted.by_task()
    o_ev = observed.by_task()
    p_start = _norm_starts(predicted)
    o_start = _norm_starts(observed)
    common = sorted(set(p_start) & set(o_start))
    only_p = sorted(set(p_start) - set(o_start))
    only_o = sorted(set(o_start) - set(p_start))

    p_rank = _ranks({t: p_start[t] for t in common})
    o_rank = _ranks({t: o_start[t] for t in common})

    per_task: Dict[int, float] = {}
    rank_skew: Dict[int, float] = {}
    agree = 0
    kinds: Dict[str, List[int]] = {}
    for t in common:
        per_task[t] = o_start[t] - p_start[t]
        rank_skew[t] = o_rank[t] - p_rank[t]
        if p_ev[t].worker == o_ev[t].worker:
            agree += 1
        kinds.setdefault(o_ev[t].name, []).append(t)

    def _mean(vals):
        vals = list(vals)
        return sum(vals) / len(vals) if vals else 0.0

    per_kind = {
        kind: {
            "n": float(len(ts)),
            "mean_abs_skew": _mean(abs(per_task[t]) for t in ts),
            "mean_abs_rank_skew": _mean(abs(rank_skew[t]) for t in ts),
        }
        for kind, ts in kinds.items()
    }
    return ReconcileReport(
        n_predicted=len(p_start),
        n_observed=len(o_start),
        matched=len(common),
        unmatched_predicted=only_p,
        unmatched_observed=only_o,
        mean_abs_skew=_mean(abs(v) for v in per_task.values()),
        max_abs_skew=max((abs(v) for v in per_task.values()), default=0.0),
        mean_abs_rank_skew=_mean(abs(v) for v in rank_skew.values()),
        max_abs_rank_skew=max((abs(v) for v in rank_skew.values()),
                              default=0.0),
        worker_agreement=(agree / len(common)) if common else 0.0,
        per_kind=per_kind,
        per_task=per_task,
    )
