"""Benchmark driver: one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [section ...]``
Prints ``name,us_per_call,derived`` CSV lines."""
from __future__ import annotations

import sys
import time
import traceback


SECTIONS = [
    "backend_compare",
    "table2_compiler_stats",
    "fig9_decode_latency",
    "fig10_moe",
    "fig11_tp_scaling",
    "fig12_pipelining",
    "fig13_overlap",
    "fig14_worker_scaling",
    "trace_reconcile",
    "launch_reduction",
    "serving_load",
    "roofline_table",
    "perf_log",
]


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    wanted = sys.argv[1:] or SECTIONS
    failures = 0
    for name in wanted:
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        t0 = time.time()
        try:
            mod.main()
        except Exception:
            failures += 1
            print(f"{name},0,FAILED")
            traceback.print_exc()
        print(f"# [{name}] {time.time() - t0:.1f}s")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
