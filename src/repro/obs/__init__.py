"""Observability: host spans, typed task timelines, Perfetto export,
reconciliation.

``span`` (``spans.py``) times the program's host work (engine iteration,
decode step, prefill, bind) inside the profiler's session, on its clock,
and keeps the records in a bounded buffer (``spans.recorded()``).

The megakernel's trace ring (``CompileOptions.trace``) records one
``desc.TRACE_WORDS`` record per executed grid slot; this package decodes
it into a :class:`TaskTrace`, emits the *predicted* timeline from the
compiler's replays in the same schema, exports Chrome-trace JSON that
Perfetto (https://ui.perfetto.dev) loads directly, and reconciles
predicted vs observed timelines into per-task / per-kind skew reports —
the measurement layer the autotuner's cost oracle is validated against.
"""
from . import spans
from .spans import span
from .perfetto import chrome_trace, validate_chrome_trace, write_chrome_trace
from .reconcile import ReconcileReport, reconcile
from .trace import (KIND_NAMES, TaskEvent, TaskTrace, check_event_order,
                    decode_ring, predicted_task_trace, sequential_trace)

__all__ = [
    "span", "spans",
    "TaskEvent", "TaskTrace", "KIND_NAMES",
    "decode_ring", "sequential_trace", "predicted_task_trace",
    "check_event_order",
    "chrome_trace", "validate_chrome_trace", "write_chrome_trace",
    "reconcile", "ReconcileReport",
]
