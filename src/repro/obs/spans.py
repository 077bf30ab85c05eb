"""Host spans and counts inside the program, on the profiler's clock.

``with span("step.pack") as sp:`` does two things.  It enters
``jax.profiler.TraceAnnotation("mpk.step.pack")``, so the span lands in the
same profiler session as the device operations and shares their clock.  And
it appends a :class:`Span` record (name, ``time.perf_counter()`` at entry and
exit, the enclosing span's index, attributes) to a bounded in-memory buffer,
which :func:`recorded` copies out.  ``sp.set(**counts)`` attaches counts that
become known inside the span; they go to the record and to the annotation's
metadata.

The profiler session is the only switch.  Spans record only while one is
active (``TraceAnnotation.is_enabled()``); otherwise ``span`` costs that one
check, returns a shared no-op span and records nothing.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Deque, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "span", "recorded", "clear", "MAX_SPANS", "PREFIX"]

PREFIX = "mpk."
#: records kept; the oldest drop out first (a decode iteration writes eight)
MAX_SPANS = 1 << 16


class Span(NamedTuple):
    """One finished span."""
    name: str                 # "mpk."-prefixed, as in the profiler trace
    index: int                # running number of the span in this process
    parent: Optional[int]     # index of the enclosing span; None at the top
    t0: float                 # time.perf_counter() at entry
    t1: float                 # ... and at exit
    attrs: Dict[str, Any]


_buffer: Deque[Span] = collections.deque(maxlen=MAX_SPANS)
_index = itertools.count()
_local = threading.local()     # per thread: indices of the open spans


def _stack() -> List[int]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class _Recording:
    """A span entered while a profiler session is active.  Its own
    bookkeeping lies inside ``t0``..``t1``, so an enclosing span's time
    less its children's holds none of theirs."""
    __slots__ = ("name", "attrs", "index", "parent", "t0", "_ann")
    recording = True

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = PREFIX + name
        self.attrs = attrs

    def set(self, **counts) -> None:
        self.attrs.update(counts)
        self._ann.set_metadata(**counts)

    def __enter__(self) -> "_Recording":
        self.t0 = time.perf_counter()
        stack = _stack()
        self.parent = stack[-1] if stack else None
        self.index = next(_index)
        stack.append(self.index)
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        _stack().pop()
        _buffer.append(Span(self.name, self.index, self.parent, self.t0,
                            time.perf_counter(), self.attrs))


class _Off:
    """The span of every call made with no profiler session active."""
    __slots__ = ()
    recording = False

    def set(self, **counts) -> None:
        pass

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        pass


_OFF = _Off()


def span(name: str, **attrs):
    """A context manager for the span ``"mpk." + name``; recording only
    while a profiler session is active."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Recording(name, attrs)


def recorded() -> List[Span]:
    """A copy of the buffer, oldest first (spans in the order they
    closed)."""
    return list(_buffer)


def clear() -> None:
    """Empty the buffer."""
    _buffer.clear()
