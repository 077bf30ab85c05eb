"""Observability section: trace-ring overhead and predicted-vs-observed
reconciliation.

For W cells on the quickstart model, decode one step twice —
trace off, then trace on — through the megakernel backend, and report

* the wall-clock cost of the in-kernel trace ring (per-step delta),
* the ring-decode + Chrome-trace export time,
* the reconciliation of the compiler's predicted timeline against the
  kernel's observed one (mean |rank skew| and worker agreement — the
  numbers that make ``replay_partition`` a trustworthy cost oracle).

``--json PATH`` writes the table as BENCH_trace.json for the nightly
perf-trajectory artifact.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from .common import emit

WORKERS = (1, 2, 4)


def _run_cell(cfg, params, workers: int) -> dict:
    import jax.numpy as jnp  # noqa: F401

    from repro.api import compile as mpk_compile
    from repro.obs import chrome_trace, check_event_order, reconcile

    toks = np.array([3, 7], np.int32)
    lens = np.zeros((2,), np.int32)

    def step_time(trace: bool):
        prog = mpk_compile(cfg, 2, 16, backend="megakernel",
                           num_workers=workers, trace=trace)
        prog = prog.bind(params).init_state()
        prog.step(toks, lens)          # trace + upload, not timed
        t0 = time.time()
        prog.step(toks, lens)
        return time.time() - t0, prog

    dt_off, _ = step_time(False)
    dt_on, prog = step_time(True)

    t0 = time.time()
    observed = prog.trace()
    obj = chrome_trace(observed)
    dt_decode = time.time() - t0
    predicted = prog.predicted_trace()
    rep = reconcile(predicted, observed)
    problems = check_event_order(observed)
    return {
        "workers": workers,
        "step_us_off": dt_off * 1e6, "step_us_on": dt_on * 1e6,
        "trace_overhead_pct": 100.0 * (dt_on - dt_off) / max(dt_off, 1e-12),
        "decode_export_us": dt_decode * 1e6,
        "events": len(obj["traceEvents"]),
        "matched": rep.matched,
        "mean_abs_rank_skew": rep.mean_abs_rank_skew,
        "worker_agreement": rep.worker_agreement,
        "order_violations": len(problems),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    args, _ = ap.parse_known_args()

    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import init_params

    cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                              n_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    print("# trace ring: per-step overhead + predicted-vs-observed skew")
    rows = []
    for workers in WORKERS:
        r = _run_cell(cfg, params, workers)
        rows.append(r)
        emit(f"trace_static_w{workers}", r["step_us_on"],
             f"overhead={r['trace_overhead_pct']:.1f}%"
             f" rank_skew={r['mean_abs_rank_skew']:.4f}"
             f" agree={r['worker_agreement']:.2f}"
             f" matched={r['matched']}"
             f" order_violations={r['order_violations']}")
        assert r["order_violations"] == 0, \
            "observed trace violates event-counter order"
    if args.json:
        Path(args.json).write_text(json.dumps(rows, indent=2))
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
