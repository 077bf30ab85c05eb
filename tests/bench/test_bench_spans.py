"""The per-layer metrics that read the program's own spans
(``repro.obs.spans``), on a synthetic record: four iterations (decode,
mixed, decode, one that called no program) with their spans, and spans of
every name before, after and across the window's end, which the readers
must leave out."""
import sys

import pytest

import repro.obs
from bench import manifest
from bench.run import Record
from bench.serve import Iteration
from repro.obs import spans
from repro.obs.spans import Span

WINDOW = [Iteration(10.0, 11.0, []), Iteration(11.0, 12.0, []),
          Iteration(12.0, 13.0, [])]

#: (name, t0, t1, attrs, children) per engine iteration; times in seconds
DECODE_A = ("mpk.engine.step", 10.1, 10.9, {"kind": "decode"}, [
    ("mpk.engine.schedule", 10.1, 10.2, {}, []),
    ("mpk.step", 10.2, 10.8, {"row_copies": 100}, [
        ("mpk.step.pack", 10.2, 10.21, {}, []),
        ("mpk.step.launch", 10.21, 10.23, {}, []),
        ("mpk.step.wait", 10.23, 10.73, {}, []),
        ("mpk.step.readback", 10.73, 10.8, {}, [])]),
    ("mpk.engine.sample", 10.8, 10.9, {}, [])])
MIXED = ("mpk.engine.step", 11.1, 11.9, {"kind": "mixed"}, [
    ("mpk.engine.schedule", 11.1, 11.15, {}, []),
    ("mpk.prefill", 11.2, 11.5, {"width": 16}, [
        ("mpk.prefill.compute", 11.2, 11.5, {}, [])]),
    ("mpk.engine.sample", 11.5, 11.55, {}, [])])
# 20 ms between the program call and the sample: work of neither, such as
# the harness's logit capture
DECODE_B = ("mpk.engine.step", 12.1, 12.7, {"kind": "decode"}, [
    ("mpk.engine.schedule", 12.1, 12.2, {}, []),
    ("mpk.step", 12.2, 12.6, {"row_copies": 300}, [
        ("mpk.step.pack", 12.2, 12.23, {}, []),
        ("mpk.step.launch", 12.23, 12.24, {}, []),
        ("mpk.step.wait", 12.24, 12.54, {}, []),
        ("mpk.step.readback", 12.54, 12.6, {}, [])]),
    ("mpk.engine.sample", 12.62, 12.7, {}, [])])
# an iteration that called no program: no engine time of the metric's
NO_CALL = ("mpk.engine.step", 12.8, 12.9, {"kind": "idle"}, [
    ("mpk.engine.schedule", 12.8, 12.9, {}, [])])


def _shifted(tree, dt, rows=10 ** 6):
    name, t0, t1, attrs, kids = tree
    attrs = dict(attrs, row_copies=rows) if "row_copies" in attrs else attrs
    return (name, t0 + dt, t1 + dt, attrs,
            [_shifted(k, dt, rows) for k in kids])


def _flatten(trees):
    out, index = [], [0]

    def add(tree, parent):
        name, t0, t1, attrs, kids = tree
        me = index[0]
        index[0] += 1
        for k in kids:
            add(k, me)
        out.append(Span(name, me, parent, t0, t1, attrs))
    for t in trees:
        add(t, None)
    return out


OUTSIDE = [_shifted(DECODE_A, -9.0), _shifted(MIXED, 20.0),
           _shifted(DECODE_B, 0.85)]       # its engine step crosses the end
SPANS = _flatten(OUTSIDE[:1] + [DECODE_A, MIXED, DECODE_B, NO_CALL]
                 + OUTSIDE[1:])

#: the metric and its value over the window, by hand
EXPECTED = {
    # schedule + sample: (100 + 100) ms, (50 + 50) ms, (100 + 80) ms
    "engine_self_ms": 160.0,
    "program_step_ms.decode": 500.0,      # 600 ms, 400 ms
    "step_pack_ms.decode": 20.0,          # 10 ms, 30 ms
    "step_launch_ms.decode": 15.0,        # 20 ms, 10 ms
    "step_wait_ms.decode": 400.0,         # 500 ms, 300 ms
    "step_readback_ms.decode": 65.0,      # 70 ms, 60 ms
    "kernel_row_copies.decode": 200.0,    # 100, 300 rows
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_metric_reads_the_window(name, monkeypatch):
    entry = next(m for m in manifest.load()["per_layer"]
                 if m["name"] == name)
    assert entry["workloads"] == ["deepseek7b.decode"]
    assert entry["source"] in ("program_span", "program_counter")
    assert entry["better"] == "lower"
    read = manifest.reader(name)
    record = Record(None, {}, {}, WINDOW)

    monkeypatch.setattr(spans, "recorded", lambda: list(SPANS))
    assert read(record) == pytest.approx(EXPECTED[name])
    # outside the window alone there is nothing to read
    assert read(Record(None, {}, {}, [Iteration(100.0, 101.0, [])])) is None
    assert read(Record(None, {}, {}, [])) is None

    monkeypatch.setattr(spans, "recorded", lambda: [])
    assert read(record) is None

    # a program without the span module (one older than it)
    monkeypatch.delattr(repro.obs, "spans")
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert read(record) is None
