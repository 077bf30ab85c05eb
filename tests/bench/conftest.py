import sys
from pathlib import Path

# the benchmark's package lives at the repository root
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import time  # noqa: E402

import pytest  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def tiny_run():
    """One run of the harness on the CPU at a tiny size: a tiny
    configuration of a family under the limits of that family's
    benchmark configuration ``real``."""
    from bench import manifest, run

    def go(tiny, real, mix, *, seed=2 ** 31 + 101, seconds=1.0,
           control=False):
        cell = manifest.cell(manifest.load(), "deepseek7b.decode")
        conf = dict(manifest.config(tiny, DATA),
                    limits=manifest.config(real)["limits"])
        return run.run_cell(conf, manifest.traffic(mix, DATA), cell, seed,
                            seconds, False, manifest.peaks()["TPU v5 lite"],
                            control=control, t_start=time.perf_counter())
    return go
