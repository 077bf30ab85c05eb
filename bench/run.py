#!/usr/bin/env python3
"""Run one cell of the serving benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
The run makes the weights on the device from the seed, compiles the
configuration for the ``megakernel`` backend, binds it, warms up the
decode step and every prefill width, and then drives
``ServingEngine.step`` for ``--seconds`` while it timestamps arrivals and
tokens.  ``--trace 1`` records a profiler trace of the window and reports
the per-layer metrics instead of the end-to-end ones.  After the window
the plain f32 reference checks what the timed path served.  The last line
of standard output is one JSON object; the compared numbers are also the
last lines of standard error.

It exits non-zero, printing no result, unless JAX's first device is a TPU
whose kind is in ``bench/peaks.json`` and there are as many chips as the
cell asks for.  ``--control 1`` also computes the control (the reference
at one precision lower) and reports it; it is for setting the limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import check, manifest, serve, weights  # noqa: E402

#: logits columns kept of each row for ``logit_err``
KEPT_COLUMNS = 512


@dataclasses.dataclass
class Record:
    """What the per-layer metric readers read."""
    model: object
    sizes: dict
    peaks: dict
    iterations: list
    trace: object = None

    @property
    def calls(self):
        return [c for it in self.iterations for c in it.calls]


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def build_program(conf: dict):
    """The program's config for ``conf``, checked against its sizes."""
    from repro.configs import get_config

    model = manifest.model(conf["family"])
    cfg = dataclasses.replace(get_config(conf["program"]["config"]),
                              **conf["program"]["overrides"])
    bad = {k: (getattr(cfg, k), v) for k, v in
           model.program_fields(conf["model"]).items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"{conf['name']}: the program's config differs "
                         f"from the file (program, file): {bad}")
    return model, cfg


def warm_up(prog, chunk: int) -> None:
    """Compile and run every shape the window can use: the decode step,
    each power-of-two prefill width up to ``chunk`` (no state is
    written: every chunk length is 0) and a slot reset."""
    b = prog.batch
    zeros = np.zeros(b, np.int32)
    prog.step(zeros, zeros)
    n = 1
    while n <= chunk:
        prog.prefill(np.zeros((b, n), np.int32), zeros, zeros)
        n *= 2
    prog.reset_slot(0)
    prog.init_state()


def run_cell(conf: dict, mix: dict, cell: manifest.Cell, seed: int,
             seconds: float, trace: bool, peaks: dict, *,
             control: bool = False, t_start: float = T_START) -> dict:
    """One run of ``cell``; returns the result line's fields."""
    import jax

    precision = conf["precision"]["matmul"]
    with jax.default_matmul_precision(precision):
        return _run(conf, mix, cell, seed, seconds, trace, peaks, control,
                    t_start)


def _run(conf, mix, cell, seed, seconds, trace, peaks, control,
         t_start) -> dict:
    import jax

    from repro.api import compile as mpk_compile
    from repro.runtime import ServingEngine

    dev = jax.devices()[0]
    model, cfg = build_program(conf)
    sizes, srv = conf["model"], conf["serving"]
    spec = model.weight_spec(sizes)
    w = weights.make(spec, seed)
    prog = mpk_compile(cfg, srv["slots"], srv["max_seq"],
                       backend="megakernel")
    prog.bind(w)
    if dev.platform == "tpu" and prog.executor.interpret:
        raise RuntimeError("the megakernel must run compiled on a TPU")
    warm_up(prog, srv["chunk"])
    engine = ServingEngine(prog, chunk=srv["chunk"], prefill_mode="chunked")
    vocab = sizes["vocab_size"]
    cols = np.sort(np.random.default_rng([seed, 3]).choice(
        vocab, size=min(KEPT_COLUMNS, vocab), replace=False))
    rec = serve.Recorder(engine, cols)
    rec.instrument(prog)
    drv = serve.Driver(engine, rec, mix, seed, vocab, horizon_s=seconds)
    if mix["loop"] == "closed":
        drv.prefill_clients()

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(secs)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    n_before = len(compiles)
    pauses = []
    gc_timer = _gc_timer(pauses)
    gc.callbacks.append(gc_timer)
    setup_s = time.perf_counter() - t_start
    start, end = drv.window(seconds)
    gc.callbacks.remove(gc_timer)
    in_window = len(compiles) - n_before
    if trace:
        jax.profiler.stop_trace()
    e2e = serve.end_to_end(drv, start, end)
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    out = {"attempted": serve.attempted(drv, start, end), "failed": 0}

    if trace:
        from bench import tracefile

        path = tracefile.find_xplane(tdir)
        tr = tracefile.from_planes(tracefile.planes_of(path))
        lo, hi = tr.window()
        device["busy_s"] = tracefile.busy_s(tr)
        device["window_s"] = hi - lo
        out["breakdown"] = {"device_ops": tracefile.top_ops(tr),
                            "idle_gaps": tracefile.idle_gaps(tr)}
        record = Record(model, sizes, peaks, rec.iterations, tr)
        metrics = {}
        for m in cell.per_layer:
            v = manifest.reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
            else:
                log(f"bench: {m['name']} found nothing to read")
        log("bench: trace planes " + "; ".join(
            f"{p['name']}: " + ", ".join(f"{ln['name']} {len(ln['events'])}"
                                         for ln in p["lines"])
            for p in tracefile.planes_of(path)))
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        metrics = {}
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    log(f"bench: window {e2e['window_s']:.3f} s, {e2e['tokens']} tokens, "
        f"{len(rec.iterations)} iterations, {e2e['n_gaps']} gaps, "
        f"{e2e['n_ttft']} first tokens due, backlog {len(engine.waiting)}, "
        f"compiles in window {in_window}, generator late by up to "
        f"{e2e['generator_late_max_ms']:.1f} ms")
    log("bench: stalls " + _stalls(rec.iterations, pauses))
    log("bench: end to end " + json.dumps(
        {k: v for k, v in e2e.items() if k.endswith("_ms")
         or k == "tokens_per_s"}) + f", setup_s {setup_s:.3f}")

    # ---- the check: the program is freed, the reference runs ----------
    served, captures = serve.served(drv), rec.captures
    drv = rec = engine = prog = None
    gc.collect()
    t0 = time.perf_counter()
    limits = conf["limits"]
    numbers = check.compare(
        model, sizes, w, _sample(served, seed), captures, cols,
        precision=conf["precision"]["matmul"],
        control_precision=conf["precision"]["control"] if control else "",
        bucket=srv["max_seq"])
    checks = check.verdict(numbers, limits)
    out["correct"] = check.passes(checks)
    log(f"bench: reference over {numbers['requests']} requests, "
        f"{numbers['tokens']} served tokens, {numbers['rows']} rows in "
        f"{time.perf_counter() - t0:.1f} s")
    if control:
        ctrl = check.verdict(numbers, limits, prefix="control_")
        out["control"] = {"correct": check.passes(ctrl), "checks": ctrl}
    out["checks"] = checks
    return out


def _gc_timer(pauses: list):
    """A ``gc.callbacks`` entry that appends ``(generation, seconds)`` of
    every collection to ``pauses``."""
    t0 = []

    def cb(phase, info):
        if phase == "start":
            t0[:] = [time.perf_counter()]
        elif t0:
            pauses.append((info["generation"], time.perf_counter() - t0.pop()))
    return cb


def _stalls(iterations: list, pauses: list) -> str:
    """Where the window's host time went when it was not steady: the
    slowest iteration and its Program calls, the longest time between
    two iterations, and the garbage collector's pauses."""
    if not iterations:
        return "no iterations"
    slow = max(iterations, key=lambda it: it.t1 - it.t0)
    calls = ", ".join(f"{c.kind} {1e3 * (c.t1 - c.t0):.1f}"
                      for c in slow.calls)
    between = max((b.t0 - a.t1 for a, b in zip(iterations, iterations[1:])),
                  default=0.0)
    worst = max(pauses, key=lambda p: p[1], default=(-1, 0.0))
    return (f"slowest iteration {1e3 * (slow.t1 - slow.t0):.1f} ms "
            f"({calls or 'no calls'}), longest between iterations "
            f"{1e3 * between:.1f} ms; gc {len(pauses)} collections, "
            f"{1e3 * sum(p[1] for p in pauses):.1f} ms in all, longest "
            f"{1e3 * worst[1]:.1f} ms (generation {worst[0]})")


def _sample(served: list, seed: int, cap: int = 32) -> list:
    """Every served request, or ``cap`` of them drawn from the seed with
    the one that served most tokens among them."""
    if len(served) <= cap:
        return served
    longest = max(range(len(served)), key=lambda i: len(served[i][2]))
    rest = [i for i in range(len(served)) if i != longest]
    pick = np.random.default_rng([seed, 5]).choice(rest, cap - 1,
                                                   replace=False)
    return [served[i] for i in sorted([longest, *pick])]


def emit(res: dict) -> None:
    """The compared numbers on standard error, then the result line."""
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']:.6e} (limit {c['limit']:.6e})")
    line = {k: res[k] for k in ("correct", "attempted", "failed", "metrics",
                                "device")}
    if "breakdown" in res:
        line["breakdown"] = res["breakdown"]
    if "control" in res:
        line["control"] = res["control"]
    line["checks"] = res["checks"]
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    seed = a.seed % 2 ** 64
    cell = manifest.cell(manifest.load(), a.workload)
    from repro.launch.compile_cache import use_compile_cache

    import jax

    devs = jax.devices()
    table = manifest.peaks()
    if devs[0].platform != "tpu":
        log(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
        return 2
    if devs[0].device_kind not in table:
        log(f"bench: no peaks for device kind {devs[0].device_kind!r} in "
            "bench/peaks.json")
        return 2
    if len(devs) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, found "
            f"{len(devs)}")
        return 2
    log(f"bench: compile cache {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    res = run_cell(manifest.config(cell.config),
                   manifest.traffic(cell.traffic), cell, seed, a.seconds,
                   bool(a.trace), table[devs[0].device_kind],
                   control=bool(a.control))
    emit(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
