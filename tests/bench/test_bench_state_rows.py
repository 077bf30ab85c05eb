"""``kernel_state_row_copies.decode`` reads the kernel's
``state_row_copies`` counter off the window's ``mpk.step`` spans, on a
synthetic record: the spans of two decode steps inside the window, one
before it and one across its end, and a step span of a program older than
the counter, which the reader must leave out."""
import sys

import pytest

import repro.obs
from bench import manifest
from bench.run import Record
from bench.serve import Iteration
from repro.obs import spans
from repro.obs.spans import Span

NAME = "kernel_state_row_copies.decode"
WINDOW = [Iteration(10.0, 11.0, []), Iteration(11.0, 12.0, [])]
SPANS = [
    Span("mpk.step", 0, None, 9.2, 9.8,
         {"row_copies": 10 ** 6, "state_row_copies": 10 ** 6}),
    Span("mpk.step", 1, None, 10.2, 10.8,
         {"row_copies": 500, "state_row_copies": 100}),
    Span("mpk.step.wait", 2, 3, 11.2, 11.7, {}),
    Span("mpk.step", 3, None, 11.1, 11.8,
         {"row_copies": 700, "state_row_copies": 300}),
    Span("mpk.step", 4, None, 11.85, 11.95, {"row_copies": 900}),
    Span("mpk.step", 5, None, 11.9, 12.4,
         {"row_copies": 10 ** 6, "state_row_copies": 10 ** 6}),
]


@pytest.mark.parametrize("name,expected", [(NAME, 200.0)])
def test_span_metric_reads_the_window(name, expected, monkeypatch):
    entry = next(m for m in manifest.load()["per_layer"]
                 if m["name"] == name)
    assert {"deepseek7b.decode", "mamba2.decode"} <= set(entry["workloads"])
    assert (entry["source"], entry["better"], entry["unit"]) == \
        ("program_counter", "lower", "rows")
    read = manifest.reader(name)
    record = Record(None, {}, {}, WINDOW)

    monkeypatch.setattr(spans, "recorded", lambda: list(SPANS))
    assert read(record) == pytest.approx(expected)     # 100, 300 rows
    assert read(Record(None, {}, {}, [Iteration(100.0, 101.0, [])])) is None
    assert read(Record(None, {}, {}, [])) is None

    # a program whose step spans carry row_copies alone (one older than
    # the state counter) gives nothing to read
    older = [Span(s.name, s.index, s.parent, s.t0, s.t1,
                  {k: v for k, v in s.attrs.items()
                   if k != "state_row_copies"}) for s in SPANS]
    monkeypatch.setattr(spans, "recorded", lambda: older)
    assert read(record) is None

    monkeypatch.setattr(spans, "recorded", lambda: [])
    assert read(record) is None

    # a program without the span module
    monkeypatch.delattr(repro.obs, "spans")
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert read(record) is None
