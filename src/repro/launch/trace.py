"""Trace launcher: ``mpk-trace`` / ``python -m repro.launch.trace``.

Runs a trace-enabled decode through a compiled Program, writes the
observed timeline as Perfetto-loadable Chrome-trace JSON (open it at
https://ui.perfetto.dev), optionally dumps the unified metrics snapshot,
and prints the predicted-vs-observed reconciliation report — the
measurement loop the compiler's cost oracle is validated with::

    mpk-trace --workers 4 --out trace.json --snapshot snapshot.json
    mpk-trace --scheduler dynamic --backend interpreter
"""
from __future__ import annotations

import argparse
import json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b-reduced")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=16)
    ap.add_argument("--steps", type=int, default=1,
                    help="decode steps to run (the ring holds the LAST)")
    ap.add_argument("--backend", choices=["megakernel", "interpreter"],
                    default="megakernel",
                    help="megakernel = the kernel-written trace ring; "
                         "interpreter = the sequential-execution timeline")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--scheduler", choices=["static", "dynamic"],
                    default="static")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="write the OBSERVED Chrome-trace JSON here")
    ap.add_argument("--predicted-out", default=None,
                    help="write the PREDICTED Chrome-trace JSON here")
    ap.add_argument("--snapshot", default=None,
                    help="write the unified metrics snapshot JSON here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import compile as mpk_compile
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import init_params
    from repro.obs import check_event_order, reconcile, write_chrome_trace

    use_compile_cache()

    cfg = get_config(args.arch)
    assert not cfg.embed_input, "trace demo uses token-input archs"
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(args.seed)

    prog = mpk_compile(cfg, args.batch, args.max_seq,
                       backend=args.backend, num_workers=args.workers,
                       scheduler=args.scheduler, tp=args.tp,
                       trace=True).bind(params).init_state()
    lens = np.zeros((args.batch,), np.int32)
    for _ in range(max(1, args.steps)):
        toks = np.asarray(rng.integers(1, cfg.vocab, size=args.batch),
                          np.int32)
        prog.step(toks, lens)
        lens += 1

    observed = prog.trace()
    predicted = prog.predicted_trace()
    print(f"[trace] {args.backend} backend, scheduler={args.scheduler} "
          f"W={args.workers}: {len(observed.events)} events over "
          f"{observed.makespan:.0f} ticks"
          if observed.meta.get("time_unit") == "tick" else
          f"[trace] {len(observed.events)} events")

    problems = check_event_order(observed)
    print(f"[trace] event-order check: "
          f"{'OK' if not problems else f'{len(problems)} violations'}")
    for p in problems[:5]:
        print(f"[trace]   {p}")

    print(reconcile(predicted, observed).summary())

    if args.out:
        write_chrome_trace(observed, args.out)
        print(f"[trace] observed timeline -> {args.out} "
              f"(open at https://ui.perfetto.dev)")
    if args.predicted_out:
        write_chrome_trace(predicted, args.predicted_out)
        print(f"[trace] predicted timeline -> {args.predicted_out}")
    if args.snapshot:
        with open(args.snapshot, "w") as fh:
            json.dump(prog.metrics_snapshot(), fh, indent=2)
        print(f"[trace] metrics snapshot -> {args.snapshot}")

    assert not problems, "trace inconsistent with event-counter semantics"


if __name__ == "__main__":
    main()
