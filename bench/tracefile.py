"""From a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to plain lists first (:func:`from_planes`), so the
arithmetic is checked on a small recorded fixture without a chip.  Device
operations are the events of each TPU plane's ``XLA Ops`` line; host
spans are the ``bench.*`` annotations the harness writes.  All times are
seconds on the profiler's one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from .stats import clip, gaps, union

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"

Event = Tuple[str, float, float]      # (name, start_s, end_s)


@dataclasses.dataclass
class Trace:
    device: Dict[str, List[Event]]    # plane name -> op events
    host: List[Event]                 # bench.* spans

    def window(self) -> Tuple[float, float]:
        spans = [(s, e) for n, s, e in self.host if n == WINDOW]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW} span, found "
                             f"{len(spans)}")
        return spans[0]

    def ops(self, plane: str, lo: float, hi: float) -> List[Event]:
        return [(n, s, e) for n, s, e in self.device[plane]
                if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """The HLO instruction an op event runs: a TPU trace names the event
    by the instruction's whole text (``%mpk_megakernel.1 = f32[...]
    custom-call(...)``), in which other instructions appear as operands."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def from_planes(planes: List[dict]) -> Trace:
    """``planes``: ``[{"name", "lines": [{"name", "events": [[name,
    start_ns, duration_ns], ...]}]}]``; device ops are named by
    :func:`op_name`."""
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for pl in planes:
        if DEVICE_PLANE.match(pl["name"]):
            evs = device.setdefault(pl["name"], [])
            for ln in pl["lines"]:
                if ln["name"] == OPS_LINE:
                    evs.extend((op_name(n), s * 1e-9, (s + d) * 1e-9)
                               for n, s, d in ln["events"])
        else:
            for ln in pl["lines"]:
                host.extend((n, s * 1e-9, (s + d) * 1e-9)
                            for n, s, d in ln["events"]
                            if n.startswith(SPAN_PREFIX))
    return Trace(device, host)


def planes_of(path: str) -> List[dict]:
    """The planes of an ``.xplane.pb`` file, as :func:`from_planes` takes
    them (device op lines and host annotations only)."""
    import jax

    out = []
    for pl in jax.profiler.ProfileData.from_file(path).planes:
        dev = bool(DEVICE_PLANE.match(pl.name))
        lines = []
        for ln in pl.lines:
            if dev and ln.name != OPS_LINE:
                continue
            evs = [[e.name, e.start_ns, e.duration_ns] for e in ln.events
                   if dev or e.name.startswith(SPAN_PREFIX)]
            if evs:
                lines.append({"name": ln.name, "events": evs})
        out.append({"name": pl.name, "lines": lines})
    return out


def find_xplane(logdir: str) -> Optional[str]:
    hits = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


# ----------------------------------------------------------- reductions

def busy_s(trace: Trace) -> float:
    """Seconds of the window in which some operation ran, averaged over
    the device planes."""
    lo, hi = trace.window()
    per = [sum(e - s for s, e in union(clip(
        [(s, e) for _, s, e in trace.ops(p, lo, hi)], lo, hi)))
        for p in trace.device]
    return sum(per) / len(per) if per else 0.0


def kernel_durations(trace: Trace, kernel: str) -> List[float]:
    """Durations of the operations named ``kernel`` (``kernel.<n>`` too)
    that start inside the window, over every device plane."""
    lo, hi = trace.window()
    return [e - s for p in trace.device for n, s, e in trace.ops(p, lo, hi)
            if (n == kernel or n.startswith(kernel + ".")) and s >= lo]


def top_ops(trace: Trace, k: int = 10) -> List[list]:
    """The ``k`` operation names with the most device time in the window
    (summed over events and planes, each event clipped to the window)."""
    lo, hi = trace.window()
    tot: Dict[str, float] = {}
    for p in trace.device:
        for n, s, e in trace.ops(p, lo, hi):
            tot[n] = tot.get(n, 0.0) + min(e, hi) - max(s, lo)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def _holder(spans: List[Event], t: float) -> str:
    """The innermost host span open at ``t`` (latest start), by name."""
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or s > best[1]):
            best = (n, s)
    return best[0][len(SPAN_PREFIX):] if best else "no_span"


def idle_gaps(trace: Trace, k: int = 10) -> List[list]:
    """The ``k`` longest stretches of the window with no operation on a
    device, each named by the host span open at its middle."""
    lo, hi = trace.window()
    spans = [h for h in trace.host if h[0] != WINDOW]
    found = []
    for p in trace.device:
        cover = union(clip([(s, e) for _, s, e in trace.ops(p, lo, hi)],
                           lo, hi))
        found += gaps(cover, lo, hi)
    found.sort(key=lambda g: g[0] - g[1])
    return [[_holder(spans, (s + e) / 2), e - s] for s, e in found[:k]]
