"""The reduction from a profiler trace to device time, idle share and
idle gaps, on a small recorded fixture in the layout a TPU trace has."""
import json
from pathlib import Path

import pytest

from bench import tracefile

FIXTURE = Path(__file__).resolve().parent / "data" / "trace_fixture.json"


@pytest.fixture
def trace():
    with open(FIXTURE) as f:
        return tracefile.from_planes(json.load(f))


def test_planes_and_window(trace):
    assert list(trace.device) == ["/device:TPU:0"]     # not SparseCore
    assert trace.window() == pytest.approx((1e-6, 101e-6))
    assert len(trace.host) == 6


def test_busy_is_the_union_of_ops_inside_the_window(trace):
    # 500 (copy.3 clipped at the window's start) + 33000 + 1000 + 29000 ns
    assert tracefile.busy_s(trace) == pytest.approx(63.5e-6)


def test_kernel_time(trace):
    # the fusion that reads the kernel's output names it as an operand:
    # it is not a launch of the kernel
    assert tracefile.kernel_durations(trace, "mpk_megakernel") == \
        pytest.approx([33e-6])
    assert tracefile.kernel_durations(trace, "copy") == []  # starts before


def test_top_ops(trace):
    names = [n for n, _ in tracefile.top_ops(trace)]
    assert names == ["mpk_megakernel.1", "fusion.2", "fusion.1", "copy.3"]
    assert tracefile.top_ops(trace)[3][1] == pytest.approx(0.5e-6)


def test_idle_gaps_named_by_the_host_span_open_in_them(trace):
    got = tracefile.idle_gaps(trace)
    assert [n for n, _ in got] == ["no_span", "program_prefill",
                                   "program_step", "program_step"]
    assert [t for _, t in got] == pytest.approx(
        [21.5e-6, 11e-6, 3.5e-6, 0.5e-6])
    idle = sum(t for _, t in got)
    lo, hi = trace.window()
    assert idle + tracefile.busy_s(trace) == pytest.approx(hi - lo)


def test_a_trace_without_a_window_is_refused():
    t = tracefile.from_planes([{"name": "/device:TPU:0", "lines": []}])
    with pytest.raises(ValueError):
        t.window()


def test_percentile_interpolates_as_numpy_does():
    import numpy as np

    from bench.stats import percentile

    v = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 50, 95, 100):
        assert percentile(v, q) == pytest.approx(np.percentile(v, q))
