"""The measured window: traffic into the serving engine, timestamps out.

The harness drives ``ServingEngine.step`` itself and timestamps every
arrival and every emitted token on the host clock.  It records spans
around the engine's step and around the Program calls inside it by
wrapping the bound methods of that one Program instance, and writes the
same spans into the profiler's trace as ``bench.*`` annotations.  From
each Program call it keeps the logits of a few hundred columns at the
position that produced each slot's token, for the comparison with the
reference after the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List

import numpy as np

from . import traffic


@dataclasses.dataclass
class Call:
    """One Program call inside the window."""
    kind: str                       # "step" | "prefill"
    t0: float
    t1: float
    positions: List[List[int]]      # per active slot: positions fed
    state_s: float = 0.0            # get_state + set_state inside it


@dataclasses.dataclass
class Iteration:
    t0: float
    t1: float
    calls: List[Call]


@dataclasses.dataclass
class Tracked:
    """One request as its user sees it."""
    req: object
    due: float                      # host clock: when it was due
    submitted: float
    times: List[float] = dataclasses.field(default_factory=list)


class Recorder:
    """Host spans, Program calls and captured logits of one run."""

    def __init__(self, engine, cols: np.ndarray):
        self.engine = engine
        self.cols = cols
        self.on = False             # spans and calls kept only when on
        self.iterations: List[Iteration] = []
        self.captures: List[tuple] = []   # (rid, position, values)
        self._calls: List[Call] = []
        self._state_s = 0.0

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield t0
            finally:
                self.last = (t0, time.perf_counter())

    def instrument(self, prog) -> None:
        """Wrap ``prog``'s step, prefill, get_state and set_state on the
        instance: the engine and ``prefill`` itself call through them."""
        step, prefill = prog.step, prog.prefill
        get_state, set_state = prog.get_state, prog.set_state

        def timed_state(fn, name):
            def wrapper(*a, **k):
                with self.span(name):
                    out = fn(*a, **k)
                self._state_s += self.last[1] - self.last[0]
                return out
            return wrapper

        def w_step(tokens, seq_lens, positions=None):
            with self.span("program_step"):
                logits = step(tokens, seq_lens, positions)
            self._after("step", np.asarray(seq_lens),
                        np.ones(len(seq_lens), np.int64), logits[:, None])
            return logits

        def w_prefill(tokens, seq_lens, chunk_lens=None):
            self._state_s = 0.0
            with self.span("program_prefill"):
                logits = prefill(tokens, seq_lens, chunk_lens)
            n = np.asarray(tokens).shape[1]
            lens = (np.full(len(seq_lens), n) if chunk_lens is None
                    else np.asarray(chunk_lens))
            self._after("prefill", np.asarray(seq_lens), lens, logits)
            return logits

        prog.step, prog.prefill = w_step, w_prefill
        prog.get_state = timed_state(get_state, "get_state")
        prog.set_state = timed_state(set_state, "set_state")

    def _after(self, kind, seq_lens, chunk_lens, logits) -> None:
        t0, t1 = self.last
        slots = {r.slot: r.request_id for r in self.engine.running.values()}
        fed = []
        for s, rid in sorted(slots.items()):
            n = int(chunk_lens[s])
            if n <= 0:
                continue
            first = int(seq_lens[s])
            fed.append(list(range(first, first + n)))
            self.captures.append((rid, first + n - 1,
                                  np.array(logits[s, n - 1, self.cols])))
        if self.on:
            self._calls.append(Call(kind, t0, t1, fed,
                                    self._state_s if kind == "prefill"
                                    else 0.0))

    def engine_step(self) -> None:
        with self.span("engine_step"):
            self.engine.step()
        if self.on:
            self.iterations.append(Iteration(*self.last, self._calls))
        self._calls = []


class Driver:
    """Sends a mix's requests into the engine and watches their tokens."""

    def __init__(self, engine, rec: Recorder, mix: dict, seed: int,
                 vocab: int, horizon_s: float):
        from repro.runtime import Request

        self._Request = Request
        self.engine, self.rec, self.mix = engine, rec, mix
        self.seed, self.vocab = seed, vocab
        self.open = mix["loop"] == "open"
        self.tracked: Dict[int, Tracked] = {}
        self.live: Dict[int, Tracked] = {}
        if self.open:
            self.pending = traffic.due_before(mix, seed, horizon_s)
        else:
            self.pending = []
            self._clients = traffic.stream(mix, seed)

    def submit(self, spec: traffic.Spec, due: float) -> None:
        prompt = traffic.prompt_tokens(self.seed, spec.index,
                                       spec.prompt_len, self.vocab)
        req = self._Request(spec.index, prompt,
                            max_new_tokens=spec.output_len)
        self.engine.submit(req)
        tr = Tracked(req, due, time.perf_counter())
        self.tracked[spec.index] = self.live[spec.index] = tr

    def _next_closed(self) -> None:
        self.submit(next(self._clients), time.perf_counter())

    def start_clients(self) -> None:
        for _ in range(int(self.mix["clients"])):
            self._next_closed()

    def observe(self) -> None:
        """Timestamp new tokens; a closed-loop client whose request is
        done sends its next one at once."""
        t = time.perf_counter()
        for rid, tr in list(self.live.items()):
            while len(tr.times) < len(tr.req.output):
                tr.times.append(t)
            if tr.req.done:
                del self.live[rid]
                if not self.open:
                    self._next_closed()

    def iterate(self) -> None:
        self.rec.engine_step()
        self.observe()

    def prefill_clients(self) -> None:
        """Set-up of a closed loop: step until every client's request
        has its first token."""
        self.start_clients()
        while any(not tr.times for tr in self.live.values()):
            self.iterate()

    def window(self, seconds: float) -> tuple:
        """Serve until ``seconds`` have passed; returns the window's
        ``(start, end)`` on the host clock.  The iteration running at
        ``seconds`` completes, so the window ends with it."""
        import jax

        rec = self.rec
        with jax.profiler.TraceAnnotation("bench.window"):
            start = time.perf_counter()
            rec.on = True
            while time.perf_counter() - start < seconds:
                now = time.perf_counter() - start
                while self.open and self.pending and \
                        self.pending[0].due_s <= now:
                    spec = self.pending.pop(0)
                    self.submit(spec, start + spec.due_s)
                if not self.engine.running and not self.engine.waiting:
                    nxt = (self.pending[0].due_s if self.open
                           and self.pending else seconds)
                    with rec.span("wait_arrival"):
                        time.sleep(max(0.0, min(nxt, seconds) - now))
                    continue
                self.iterate()
            end = time.perf_counter()
            rec.on = False
        while self.open and self.pending and \
                self.pending[0].due_s < end - start:   # due, never served
            spec = self.pending.pop(0)
            self.submit(spec, start + spec.due_s)
        return start, end


# ------------------------------------------------------------ end to end

def end_to_end(drv: Driver, start: float, end: float) -> Dict[str, object]:
    """Tokens, gaps and first-token waits of the window, from the
    harness's own timestamps."""
    from .stats import percentile

    tokens, itl, ttft = 0, [], []
    for tr in drv.tracked.values():
        ts = tr.times
        tokens += sum(1 for t in ts if start < t <= end)
        itl += [b - a for a, b in zip(ts, ts[1:]) if start < b <= end]
        if ts and not tr.req.done and ts[-1] <= end:
            itl.append(end - ts[-1])              # the open gap
        if drv.open and start <= tr.due < end:
            first = ts[0] if ts else None
            ttft.append((first if first is not None and first <= end
                         else end) - tr.due)
    span = end - start
    out = {"tokens": tokens, "window_s": span,
           "tokens_per_s": tokens / span,
           "n_gaps": len(itl), "n_ttft": len(ttft)}
    if itl:
        out["itl_p95_ms"] = 1e3 * percentile(itl, 95)
        out["itl_p50_ms"] = 1e3 * percentile(itl, 50)
    if ttft:
        out["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
        out["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
    late = [tr.submitted - tr.due for tr in drv.tracked.values()
            if drv.open and start <= tr.due < end]
    out["generator_late_max_ms"] = 1e3 * max(late, default=0.0)
    if len(ttft) >= 6:        # a queue that grows makes later waits longer
        third = len(ttft) // 3
        out["ttft_first_third_ms"] = 1e3 * sum(ttft[:third]) / third
        out["ttft_last_third_ms"] = 1e3 * sum(ttft[-third:]) / third
    return out


def attempted(drv: Driver, start: float, end: float) -> int:
    """Requests the window was asked to serve: due in it (open loop) or
    in flight during it (closed loop)."""
    if drv.open:
        return sum(1 for tr in drv.tracked.values() if start <= tr.due < end)
    return sum(1 for tr in drv.tracked.values()
               if tr.submitted < end and (not tr.req.done
                                          or tr.times[-1] > start))


def served(drv: Driver) -> List[tuple]:
    """``(rid, prompt, output)`` of every request with a served token."""
    return [(rid, list(tr.req.prompt), list(tr.req.output))
            for rid, tr in sorted(drv.tracked.items()) if tr.req.output]


