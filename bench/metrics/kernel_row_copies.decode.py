"""Single-row copies the megakernel makes per decode launch: the kernel's
own ``row_copies`` counter, which the ``mpk.step`` span carries."""
from bench.spans import window_spans


def read(record):
    n = [s.attrs["row_copies"] for s in window_spans(record) or ()
         if s.name == "mpk.step" and "row_copies" in s.attrs]
    return sum(n) / len(n) if n else None
