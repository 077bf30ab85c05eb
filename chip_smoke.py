#!/usr/bin/env python3
"""Smoke test of the serving path on one TPU chip.

Serves deepseek-7b at its published widths, cut to one layer, through the
compiled megakernel: ``mpk.compile(..., backend="megakernel")`` driven by
the continuous-batching ``ServingEngine``, exactly as ``mpk-serve`` builds
it.  Then checks the kernel's logits against the plain f32 reference (the
``jax`` backend at ``"highest"`` matmul precision) from one prefilled
state.

    python chip_smoke.py [--seed N]

Exits non-zero, printing no result, unless JAX's first device is a TPU.
The last line of output is ``{"ok": true, "device": {...}}``.  Every time
printed is a smoke timing of this run, not a benchmark number.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

SLOTS = 8
MAX_SEQ = 1024
N_REQUESTS = 8
PROMPT_LENS = (128, 512)
MAX_NEW = 32
CHUNK = 128
PARITY_STEPS = 4


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the traffic")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    from repro.api import compile as mpk_compile
    from repro.api.parity import PARITY_TOL, decode_parity
    from repro.configs import get_config
    from repro.launch.serve import poisson_workload, run_engine
    from repro.models import init_params

    full = get_config("deepseek-7b")
    cfg = dataclasses.replace(full, n_layers=1)
    print(f"device: {dev.device_kind} x{len(jax.devices())}")
    print(f"config: {cfg.name} n_layers {full.n_layers} -> 1 (one period "
          f"of the dense stack); widths as published: d_model "
          f"{cfg.d_model}, {cfg.n_heads} q / {cfg.n_kv_heads} kv heads of "
          f"{cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab}")
    print(f"compile cache: {cache_dir}")

    t0 = time.perf_counter()
    params = init_params(cfg, jax.random.PRNGKey(args.seed),
                         dtype=jnp.float32)
    prog = mpk_compile(cfg, SLOTS, MAX_SEQ, backend="megakernel")
    prog.bind(params)
    ex = prog.executor
    check(not ex.interpret, "the megakernel must run compiled on a TPU")
    n_custom = ex.lowered_step().as_text().count("tpu_custom_call")
    t_setup = time.perf_counter() - t0
    print(f"heap: {prog.plan.heap_size} words = "
          f"{4 * prog.plan.heap_size / 1e9:.3f} GB, {prog.plan.num_steps} "
          f"tasks per launch, {n_custom} tpu_custom_call in the lowered "
          f"step")
    check(n_custom >= 1, "the decode step holds no compiled kernel")

    # ---- serve phase: the mpk-serve path --------------------------------
    rng = np.random.default_rng(args.seed)
    reqs = poisson_workload(rng, N_REQUESTS, PROMPT_LENS[0], MAX_NEW,
                            cfg.vocab, 0.0, prompt_len_max=PROMPT_LENS[1])
    t0 = time.perf_counter()
    engine = run_engine(cfg, params, reqs, slots=SLOTS, max_seq=MAX_SEQ,
                        chunk=CHUNK, prefill_mode="chunked",
                        backend="megakernel", program=prog)
    t_serve = time.perf_counter() - t0
    done = engine.finished
    ctr = ex.pipeline_counters()
    print(f"serve: {len(done)}/{N_REQUESTS} requests finished, prompts "
          f"{sorted(len(r.prompt) for r in reqs)}, "
          f"{sum(len(r.output) for r in done)} tokens, "
          f"{engine.iterations} iterations, {engine.decode_iterations} "
          f"in-kernel decode steps")
    print(f"kernel counters (last launch): bulk_copies "
          f"{ctr['bulk_copies']}, row_copies {ctr['row_copies']}, "
          f"prefetch_tiles {ctr['prefetch_tiles']}, event_wait_violations "
          f"{ctr['event_wait_violations']}")
    check(len(done) == N_REQUESTS
          and all(len(r.output) == MAX_NEW for r in done),
          "not every request finished")
    check(engine.decode_iterations > 0, "no decode step ran in the kernel")
    check(ctr["bulk_copies"] > 0, "the kernel issued no tile DMA")
    check(ctr["event_wait_violations"] == 0, "event-wait violations")

    # ---- parity phase: kernel vs the plain f32 reference ----------------
    t0 = time.perf_counter()
    prog.init_state()
    prompt = rng.integers(1, cfg.vocab, size=(SLOTS, CHUNK), dtype=np.int32)
    lens = rng.integers(CHUNK // 2, CHUNK + 1, size=SLOTS, dtype=np.int32)
    prog.prefill(prompt, np.zeros(SLOTS, np.int32), lens)
    ref = mpk_compile(cfg, SLOTS, MAX_SEQ, backend="jax").bind(params)
    tokens = rng.integers(1, cfg.vocab, size=(PARITY_STEPS, SLOTS),
                          dtype=np.int32)
    errs = decode_parity(prog, ref, tokens, lens)
    t_parity = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i, t in enumerate(tokens):          # logits reach the host
        prog.step(t, lens + PARITY_STEPS + i)
    t_step = (time.perf_counter() - t0) / PARITY_STEPS
    print(f"parity over {PARITY_STEPS} decode steps, max|kernel - ref| / "
          f"max|ref|: {errs['highest']:.3e} vs f32 reference (highest), "
          f"{errs['default']:.3e} vs the same at DEFAULT precision; "
          f"tolerance {PARITY_TOL:.0e}")
    check(errs["highest"] <= PARITY_TOL, "kernel logits diverge from f32")
    check(errs["default"] > PARITY_TOL,
          "the tolerance cannot tell f32 from a bf16 matmul pass")

    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"peak device memory: {peak / 1e9:.3f} GB")
    print(f"compile: {sum(compile_s):.1f} s in {len(compile_s)} "
          f"compilations (persistent-cache reads included)")
    print(f"smoke timings, not benchmark numbers: setup {t_setup:.1f} s, "
          f"serve {t_serve:.1f} s, parity {t_parity:.1f} s, decode step "
          f"{t_step * 1e3:.1f} ms")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
