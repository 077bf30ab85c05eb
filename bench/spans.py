"""What the per-layer metrics that read the program's own spans share.

The program records host spans (``repro.obs.spans``) while a profiler
session is active, on the same ``time.perf_counter`` clock as the
harness's iterations.  A reader keeps the spans that lie inside the window,
which runs from the first to the last of ``record.iterations``.  A program
that records no spans, or none in the window, gives nothing to read: the
readers then return ``None``.
"""
from __future__ import annotations

from typing import List, Optional


def window_spans(record) -> Optional[List]:
    """The program's span records inside the window, or ``None`` where
    the program keeps none."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    if not record.iterations:
        return None
    lo, hi = record.iterations[0].t0, record.iterations[-1].t1
    return [s for s in spans.recorded() if s.t0 >= lo and s.t1 <= hi]


def mean_span_ms(record, name: str) -> Optional[float]:
    """Mean duration of the window's spans named ``name``, in ms."""
    d = [s.t1 - s.t0 for s in window_spans(record) or () if s.name == name]
    return 1e3 * sum(d) / len(d) if d else None
