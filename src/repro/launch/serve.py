"""Serving launcher: ``mpk-serve`` / ``python -m repro.launch.serve``.

Continuous-batching engine over a compiled ``repro.api.Program``:
``--backend {jax,interpreter,megakernel}`` picks the execution backend
(pure-decode iterations run inside it; the megakernel backend serves
them as single persistent-kernel launches against its device-resident
heap).  Chunked prefill (``--chunk`` prompt tokens per iteration,
``--prefill-mode token`` for the legacy one-token baseline),
page-pressure preemption (``--total-pages`` oversubscribes the KV page
pool), and per-request latency metrics (TTFT / TPOT / queue time) over a
Poisson-arrival workload (``--arrival-rate`` req/s; 0 = all requests
arrive at t=0).  ``--crosscheck`` additionally decodes a prefilled batch
through the backend and the f32 reference and asserts logits parity
(``api.parity.decode_parity``).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def poisson_workload(rng: np.random.Generator, n_requests: int,
                     prompt_len: int, max_new: int, vocab: int,
                     arrival_rate: float,
                     prompt_len_max: Optional[int] = None
                     ) -> List["Request"]:
    """Requests with exponential inter-arrival gaps (a Poisson process);
    ``arrival_rate <= 0`` degenerates to an offline batch at t=0.  Prompt
    lengths are ``prompt_len``, or uniform in ``[prompt_len,
    prompt_len_max]`` when that is given."""
    from repro.runtime import Request

    t = 0.0
    reqs = []
    for rid in range(n_requests):
        if arrival_rate > 0:
            t += float(rng.exponential(1.0 / arrival_rate))
        n = prompt_len if prompt_len_max is None else int(
            rng.integers(prompt_len, prompt_len_max + 1))
        prompt = rng.integers(1, vocab, size=n).tolist()
        reqs.append(Request(rid, prompt, max_new_tokens=max_new,
                            arrival_time=t))
    return reqs


def run_engine(cfg, params, reqs, *, slots: int, max_seq: int,
               chunk: int, prefill_mode: str, page_size: int = 32,
               total_pages: Optional[int] = None,
               token_budget: Optional[int] = None,
               backend: str = "jax", step_cache=None, program=None):
    from repro.runtime import ServingEngine

    if program is None:
        engine = ServingEngine.from_model(
            cfg, params, max_slots=slots, max_seq=max_seq,
            backend=backend, step_cache=step_cache, chunk=chunk,
            prefill_mode=prefill_mode, page_size=page_size,
            total_pages=total_pages, token_budget=token_budget)
    else:
        assert program.backend == backend, (
            f"program backend {program.backend!r} != requested {backend!r}")
        engine = ServingEngine(program, chunk=chunk,
                               prefill_mode=prefill_mode,
                               page_size=page_size,
                               total_pages=total_pages,
                               token_budget=token_budget)
    for r in reqs:
        engine.submit(r)
    engine.run()
    return engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b-reduced")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--prefill-mode", choices=["chunked", "token"],
                    default="chunked")
    ap.add_argument("--token-budget", type=int, default=None)
    ap.add_argument("--page-size", type=int, default=32,
                    help="KV page granularity (must divide --max-seq)")
    ap.add_argument("--total-pages", type=int, default=None,
                    help="oversubscribe the KV page pool (forces "
                         "preemption under load)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrival rate in req/s (0 = offline)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=["jax", "interpreter",
                                          "megakernel"], default="jax",
                    help="Program execution backend for decode steps")
    ap.add_argument("--workers", type=int, default=1,
                    help="decentralized worker lanes the compiled "
                         "schedule is partitioned onto")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile all jit step widths on a throwaway "
                         "engine so the reported TTFT/TPOT measure the "
                         "schedule, not XLA compile time")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the unified metrics snapshot (program + "
                         "compiler + pipeline + workers + serving) as "
                         "JSON to PATH")
    ap.add_argument("--trace-json", default=None, metavar="PATH",
                    help="dump the task timeline as Chrome-trace JSON to "
                         "PATH (megakernel backend: compiles with "
                         "trace=True and exports the kernel's heap ring; "
                         "other backends export their own timeline)")
    ap.add_argument("--crosscheck", "--megakernel", dest="crosscheck",
                    action="store_true",
                    help="decode a prefilled batch through the backend "
                         "and the f32 reference and assert logits parity "
                         "(--megakernel is the deprecated alias)")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import init_params

    use_compile_cache()

    cfg = get_config(args.arch)
    assert not cfg.embed_input, "serve demo uses token-input archs"
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(args.seed)

    reqs = poisson_workload(rng, args.requests, args.prompt_len,
                            args.max_new, cfg.vocab, args.arrival_rate)
    step_cache: dict = {}
    # compile ONCE; the warmup and timed runs share the same Program so
    # the timed TTFT/TPOT never include the jit/pallas trace
    from repro.api import compile as mpk_compile
    program = mpk_compile(cfg, args.slots, args.max_seq,
                          backend=args.backend,
                          num_workers=args.workers,
                          step_cache=step_cache,
                          trace=args.trace_json is not None).bind(params)
    if args.warmup:
        warm = poisson_workload(np.random.default_rng(args.seed),
                                args.requests, args.prompt_len,
                                args.max_new, cfg.vocab, args.arrival_rate)
        run_engine(cfg, params, warm, slots=args.slots,
                   max_seq=args.max_seq, chunk=args.chunk,
                   prefill_mode=args.prefill_mode,
                   page_size=args.page_size,
                   total_pages=args.total_pages,
                   token_budget=args.token_budget,
                   backend=args.backend, program=program)
    steps0 = program.step_count
    scatters0 = (program.executor.state_scatter_count
                 if args.backend == "megakernel" else 0)
    t0 = time.time()
    engine = run_engine(cfg, params, reqs, slots=args.slots,
                        max_seq=args.max_seq, chunk=args.chunk,
                        prefill_mode=args.prefill_mode,
                        page_size=args.page_size,
                        total_pages=args.total_pages,
                        token_budget=args.token_budget,
                        backend=args.backend, program=program)
    dt = time.time() - t0
    done = engine.finished
    tokens = sum(len(r.output) for r in done)
    print(f"[serve] {len(done)} requests, {tokens} tokens, "
          f"{engine.iterations} iterations "
          f"({engine.decode_iterations} pure-decode via "
          f"{args.backend}) in {dt:.1f}s "
          f"({tokens / max(dt, 1e-9):.1f} tok/s, "
          f"prefill={args.prefill_mode} chunk={engine.chunk})")
    if args.backend == "megakernel":
        prog = engine.program
        print(f"[serve] megakernel program: {prog.trace_count} jit trace, "
              f"{prog.upload_count} full weight upload, "
              f"{prog.executor.state_scatter_count - scatters0} state "
              f"scatters (prefill), {prog.step_count - steps0} in-kernel "
              f"decode steps this run")
        if prog.step_count > 0:
            ws = prog.worker_stats
            print(f"[serve] workers: W={ws['num_workers']} "
                  f"event_waits={ws.get('event_waits', 0)} "
                  f"violations={ws.get('event_wait_violations', 0)} "
                  f"signals={ws.get('event_signals', 0)}")
    summary = engine.metrics_summary()
    for key in ("ttft", "queue", "tpot"):
        if f"{key}_mean_s" in summary:
            print(f"[serve] {key}: mean {summary[f'{key}_mean_s']*1e3:.1f}ms"
                  f"  p50 {summary[f'{key}_p50_s']*1e3:.1f}ms"
                  f"  p95 {summary[f'{key}_p95_s']*1e3:.1f}ms")
    print(f"[serve] preemptions: {int(summary['preemptions'])}")
    for r in done[:3]:
        print(f"  req {r.request_id}: {r.output[:8]}...")

    if args.metrics_json:
        with open(args.metrics_json, "w") as fh:
            json.dump(engine.metrics_snapshot(), fh, indent=2)
        print(f"[serve] metrics snapshot -> {args.metrics_json}")
    if args.trace_json:
        from repro.obs import write_chrome_trace

        prog = engine.program
        try:
            tl = prog.trace()   # kernel ring / interpreter timeline
        except ValueError:
            tl = prog.predicted_trace()  # e.g. no in-kernel decode step
        write_chrome_trace(tl, args.trace_json)
        print(f"[serve] {tl.origin} task timeline "
              f"({len(tl.events)} events) -> {args.trace_json} "
              f"(open at https://ui.perfetto.dev)")

    if args.crosscheck:
        from repro.api import compile as mpk_compile
        from repro.api.parity import PARITY_TOL, decode_parity

        # prefill one chunk per slot through the served program, then
        # decode the same tokens through it and the f32 reference
        program.init_state()
        n = min(args.chunk, args.max_seq // 2)
        prompt = rng.integers(1, cfg.vocab, size=(args.slots, n),
                              dtype=np.int32)
        lens = rng.integers(1, n + 1, size=args.slots, dtype=np.int32)
        program.prefill(prompt, np.zeros(args.slots, np.int32), lens)
        ref = mpk_compile(cfg, args.slots, args.max_seq,
                          backend="jax").bind(params)
        toks = rng.integers(1, cfg.vocab, size=(4, args.slots),
                            dtype=np.int32)
        errs = decode_parity(program, ref, toks, lens)
        print(f"[serve] crosscheck: 4-step decode on {args.backend}, "
              f"max|logits - ref| / max|ref| = {errs['highest']:.2e} vs "
              f"the f32 reference, {errs['default']:.2e} at DEFAULT "
              f"matmul precision (tolerance {PARITY_TOL:.0e})")
        assert errs["highest"] <= PARITY_TOL, "backend logits diverged"


if __name__ == "__main__":
    main()
