"""Chrome-trace (Perfetto) export of a :class:`~repro.obs.trace.TaskTrace`.

Emits the Trace Event Format JSON that https://ui.perfetto.dev (and
chrome://tracing) loads directly: one process per chip, one thread
track per worker lane, every task an "X" complete event, and multichip
COMM pairs (remote_copy send → allreduce-chunk recv) connected by
"s"/"f" flow arrows across chips through their shared event id.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List

from ..kernels.megakernel.desc import KIND_CODES
from .trace import TaskTrace

__all__ = ["chrome_trace", "validate_chrome_trace", "write_chrome_trace"]

_SEND_KIND = KIND_CODES["remote_copy"]

#: observed traces carry logical ticks; scale one tick to 1us so
#: Perfetto's timeline (which assumes microseconds) renders readably
_TICK_US = 1.0
#: predicted traces carry roofline seconds
_S_TO_US = 1e6


def chrome_trace(trace: TaskTrace) -> Dict[str, Any]:
    """The trace as a Trace Event Format object (``traceEvents`` +
    metadata), ready for ``json.dump``."""
    scale = _S_TO_US if trace.meta.get("time_unit") == "s" else _TICK_US
    events: List[Dict[str, Any]] = []

    for chip in range(max(1, trace.n_chips)):
        events.append({"ph": "M", "name": "process_name", "pid": chip,
                       "args": {"name": f"chip{chip}"}})
    seen_lanes = sorted({(e.chip, e.worker) for e in trace.events})
    for chip, w in seen_lanes:
        events.append({"ph": "M", "name": "thread_name", "pid": chip,
                       "tid": w, "args": {"name": f"worker{w}"}})

    for e in trace.events:
        args: Dict[str, Any] = {"task": e.task, "row": e.row,
                                "kind": e.kind}
        if e.wait_ev >= 0:
            args["wait_ev"] = e.wait_ev
            args["wait_cnt"] = e.wait_cnt
        if e.sig_ev >= 0:
            args["sig_ev"] = e.sig_ev
        events.append({
            "ph": "X", "name": e.name, "cat": trace.origin,
            "pid": e.chip, "tid": e.worker,
            "ts": e.start * scale,
            "dur": max((e.end - e.start) * scale, 1e-3),
            "args": args,
        })

    # ---- cross-chip flow arrows: a COMM send signals the event its
    # peer's recv waits on; pair them through that shared event id ----
    if trace.n_chips > 1:
        recv_by_wait = {}
        for e in trace.events:
            if e.kind != _SEND_KIND and e.wait_ev >= 0:
                recv_by_wait.setdefault(e.wait_ev, e)
        flow = 0
        for e in trace.events:
            if e.kind != _SEND_KIND or e.sig_ev < 0:
                continue
            r = recv_by_wait.get(e.sig_ev)
            if r is None or r.chip == e.chip:
                continue
            flow += 1
            events.append({"ph": "s", "id": flow, "name": "comm",
                           "cat": "comm", "pid": e.chip, "tid": e.worker,
                           "ts": e.end * scale})
            events.append({"ph": "f", "id": flow, "name": "comm",
                           "cat": "comm", "pid": r.chip, "tid": r.worker,
                           "ts": r.start * scale, "bp": "e"})

    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "origin": trace.origin,
            "num_workers": trace.num_workers,
            "n_chips": trace.n_chips,
            **{k: v for k, v in trace.meta.items()},
        },
    }


def validate_chrome_trace(obj: Any) -> List[str]:
    """Schema check of a Chrome-trace object (or its JSON string);
    returns a list of problems (empty = valid).  Covers the subset the
    exporter emits: "X" needs ts/dur/name/pid/tid, "M" needs name/args,
    "s"/"f" need matching ids and timestamps."""
    problems: List[str] = []
    if isinstance(obj, str):
        try:
            obj = json.loads(obj)
        except json.JSONDecodeError as exc:
            return [f"not JSON: {exc}"]
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["missing traceEvents"]
    flows: Dict[Any, List[str]] = {}
    for i, ev in enumerate(obj["traceEvents"]):
        if not isinstance(ev, dict) or "ph" not in ev:
            problems.append(f"event {i}: not a phase dict")
            continue
        ph = ev["ph"]
        if ph == "X":
            for key in ("name", "ts", "dur", "pid", "tid"):
                if key not in ev:
                    problems.append(f"event {i}: X missing {key!r}")
            if ev.get("dur", 0) <= 0:
                problems.append(f"event {i}: non-positive dur")
        elif ph == "M":
            for key in ("name", "args"):
                if key not in ev:
                    problems.append(f"event {i}: M missing {key!r}")
        elif ph in ("s", "f"):
            if "id" not in ev or "ts" not in ev:
                problems.append(f"event {i}: flow missing id/ts")
            else:
                flows.setdefault(ev["id"], []).append(ph)
        else:
            problems.append(f"event {i}: unknown phase {ph!r}")
    for fid, phases in flows.items():
        if sorted(phases) != ["f", "s"]:
            problems.append(f"flow {fid}: unpaired phases {phases}")
    try:
        json.dumps(obj)
    except (TypeError, ValueError) as exc:
        problems.append(f"not JSON-serializable: {exc}")
    return problems


def write_chrome_trace(trace: TaskTrace, path: str) -> Dict[str, Any]:
    """Export ``trace`` to ``path`` as Perfetto-loadable JSON; returns
    the exported object (already validated)."""
    obj = chrome_trace(trace)
    problems = validate_chrome_trace(obj)
    assert not problems, problems
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh)
    return obj
