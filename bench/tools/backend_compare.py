#!/usr/bin/env python3
"""One decode step of a cell's configuration: the megakernel against the
``jax`` backend (one XLA program of ordinary operators), on the chip.

    python bench/tools/backend_compare.py [--config deepseek-7b.L1] [--steps 20]

Both run from one state with every slot at a live length drawn as the
decode cell's prompts are, and both are timed from the call to
``block_until_ready`` on the logits, which stay on the device; the
persistent kernel's step is its jitted launch with the per-step inputs
written into the heap.  The ``jax`` backend runs at the configuration's
matmul precision and, for comparison, at ``default``.  Not a cell: the
numbers go to PERF.md by hand.
"""
from __future__ import annotations

import argparse
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import manifest, run, weights  # noqa: E402


def timed(fn, steps):
    fn()                                   # compile + warm
    out = []
    for _ in range(steps):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="deepseek-7b.L1")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from repro.api import compile as mpk_compile
    from repro.launch.compile_cache import use_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("backend_compare: needs a TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    conf = manifest.config(a.config)
    model, cfg = run.build_program(conf)
    srv = conf["serving"]
    b, seq = srv["slots"], srv["max_seq"]
    w = weights.make(model.weight_spec(conf["model"]), a.seed)
    rng = np.random.default_rng(a.seed)
    lens = rng.integers(32, 257, size=b).astype(np.int32)
    tok = rng.integers(1, conf["model"]["vocab_size"], size=b).astype(
        np.int32)
    res = {}
    prec = conf["precision"]["matmul"]
    with jax.default_matmul_precision(prec):
        prog = mpk_compile(cfg, b, seq, backend="megakernel").bind(w)
        ex = prog.executor
        vals = ex._pack_step_inputs(tok, lens)

        def mk():
            ex._heap, lg = ex._jstep(ex._heap, vals)
            lg.block_until_ready()
        res["megakernel"] = timed(mk, a.steps)
        ex._heap = None
        prog = None
    for p in (prec, "default"):
        with jax.default_matmul_precision(p):
            jp = mpk_compile(cfg, b, seq, backend="jax").bind(w)
            jp.init_state()
            fn = jp._prefill_fn(1)
            args = (jnp.asarray(tok[:, None]), jnp.asarray(lens),
                    jnp.ones((b,), jnp.int32))

            def xla():
                lg, jp._cache = fn(jp._params, jp._cache, *args)
                lg.block_until_ready()
            res[f"jax@{p}"] = timed(xla, a.steps)
            jp = None
    print(f"device: {dev.device_kind} x{len(jax.devices())}; {a.config}, "
          f"{b} slots at live lengths {lens.tolist()}, {a.steps} steps")
    for k, v in res.items():
        print(f"{k}: median {1e3 * statistics.median(v):.3f} ms, min "
              f"{1e3 * min(v):.3f} ms, max {1e3 * max(v):.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
