"""The engine's own host time per serving iteration that called the
program: the ``mpk.engine.schedule`` and ``mpk.engine.sample`` children of
an ``mpk.engine.step`` span with an ``mpk.step`` or ``mpk.prefill`` child.
What else runs inside the iteration, such as work wrapped around the
program call from outside, is left out."""
from bench.spans import window_spans

OWN = ("mpk.engine.schedule", "mpk.engine.sample")
CALLS = ("mpk.step", "mpk.prefill")


def read(record):
    own, called = {}, set()
    for s in window_spans(record) or ():
        if s.name in OWN:
            own[s.parent] = own.get(s.parent, 0.0) + s.t1 - s.t0
        elif s.name in CALLS and s.parent is not None:
            called.add(s.parent)
    ms = [own[i] for i in called if i in own]
    return 1e3 * sum(ms) / len(ms) if ms else None
