"""Single-row copies of state the megakernel makes per decode launch (SSM
and conv state tiles, K/V cache rows): the kernel's own
``state_row_copies`` counter, which the ``mpk.step`` span carries."""
from bench.spans import window_spans


def read(record):
    n = [s.attrs["state_row_copies"] for s in window_spans(record) or ()
         if s.name == "mpk.step" and "state_row_copies" in s.attrs]
    return sum(n) / len(n) if n else None
