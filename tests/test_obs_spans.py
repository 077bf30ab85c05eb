"""Host spans (``repro.obs.spans``): recorded only inside a profiler session,
nested by parent links, written into the same ``.xplane.pb`` as the device
ops, bounded in memory; and the spans the engine, the program and the
megakernel executor write, on a tiny megakernel in interpret mode."""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpk
from repro import obs
from repro.configs import get_config
from repro.models import init_params
from repro.obs import spans
from repro.runtime import Request, ServingEngine


@pytest.fixture(autouse=True)
def empty_buffer():
    spans.clear()
    yield
    spans.clear()


def _names(records):
    return [s.name for s in records]


def _xplane_spans(logdir):
    """``{name: stats}`` of the ``mpk.*`` events in a written profile."""
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spans.PREFIX):
                    out[ev.name] = dict(ev.stats)
    return out


def test_no_session_records_nothing():
    with obs.span("outer", iteration=1) as sp:
        sp.set(row_copies=7)
        with obs.span("inner") as inner:
            assert not inner.recording
    assert not sp.recording
    assert spans.recorded() == []


def test_nested_spans_record_parents_attrs_and_reach_the_profile(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("outer", iteration=3, kind="decode") as outer:
            with obs.span("inner") as inner:
                jnp.ones(8).block_until_ready()
                inner.set(row_copies=123)
            with obs.span("inner2"):
                pass
            assert outer.recording and inner.recording
    recs = {s.name: s for s in spans.recorded()}
    # records are appended as spans close: children before their parent
    assert _names(spans.recorded()) == ["mpk.inner", "mpk.inner2",
                                        "mpk.outer"]
    o, i, i2 = recs["mpk.outer"], recs["mpk.inner"], recs["mpk.inner2"]
    assert o.parent is None
    assert i.parent == o.index and i2.parent == o.index
    assert o.t0 <= i.t0 <= i.t1 <= i2.t0 <= i2.t1 <= o.t1
    assert o.attrs == {"iteration": 3, "kind": "decode"}
    assert i.attrs == {"row_copies": 123}
    assert i2.attrs == {}
    written = _xplane_spans(tmp_path)
    assert set(written) == {"mpk.outer", "mpk.inner", "mpk.inner2"}
    assert written["mpk.outer"] == {"iteration": 3, "kind": "decode"}
    assert written["mpk.inner"] == {"row_copies": 123}
    # after the session, nothing more is recorded
    with obs.span("late"):
        pass
    assert len(spans.recorded()) == 3


def test_buffer_stays_bounded(tmp_path):
    extra = 10
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(spans.MAX_SPANS + extra):
            with obs.span("tick"):
                pass
    recs = spans.recorded()
    assert len(recs) == spans.MAX_SPANS
    # the oldest dropped out: the first kept is the (extra+1)-th written
    assert recs[-1].index - recs[0].index == spans.MAX_SPANS - 1
    spans.clear()
    assert spans.recorded() == []


# ---------------------------------------------------------------------------
# The program's spans, on a tiny megakernel (Pallas interpreter on the CPU).
# ---------------------------------------------------------------------------

B, S = 2, 24


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                              n_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def test_row_copies_on_each_step_equal_the_kernel_counter(tiny, tmp_path):
    cfg, params = tiny
    prog = mpk.compile(cfg, B, S, backend="megakernel")
    with jax.profiler.trace(str(tmp_path)):
        prog.bind(params).init_state()
        lens = np.zeros((B,), np.int32)
        counters, state = [], []
        for i in range(3):
            prog.step(np.full((B,), i + 1, np.int32), lens)
            c = prog.executor.pipeline_counters()
            counters.append(c["row_copies"])
            state.append(c["state_row_copies"])
            lens += 1 + np.arange(B, dtype=np.int32)   # ragged lengths
    recs = spans.recorded()
    steps = [s for s in recs if s.name == "mpk.step"]
    assert [s.attrs["row_copies"] for s in steps] == counters
    assert [s.attrs["state_row_copies"] for s in steps] == state
    assert counters[0] > 0 and len(set(counters)) > 1  # K/V rows grow
    assert 0 < state[0] < counters[0] and len(set(state)) > 1
    names = set(_names(recs))
    assert {"mpk.bind.heap", "mpk.bind.upload"} <= names
    # the four children of every step, in order, inside it
    for st in steps:
        kids = [s for s in recs if s.parent == st.index]
        assert _names(kids) == ["mpk.step.pack", "mpk.step.launch",
                                "mpk.step.wait", "mpk.step.readback"]
        assert all(st.t0 <= k.t0 <= k.t1 <= st.t1 for k in kids)
    written = _xplane_spans(tmp_path)
    assert written["mpk.step"] == {"row_copies": counters[-1],
                                   "state_row_copies": state[-1]}


def test_decode_step_logits_bitwise_same_recording_on_and_off(tiny,
                                                              tmp_path):
    cfg, params = tiny
    prog = mpk.compile(cfg, B, S, backend="megakernel").bind(params)
    toks = np.array([5, 9], np.int32)

    def two_steps():
        prog.init_state()
        lens = np.array([0, 0], np.int32)
        a = prog.step(toks, lens)
        b = prog.step(toks + 1, lens + 1)
        return a, b

    off = two_steps()
    assert spans.recorded() == []
    with jax.profiler.trace(str(tmp_path)):
        on = two_steps()
    assert len([s for s in spans.recorded() if s.name == "mpk.step"]) == 2
    for x, y in zip(off, on):
        assert x.dtype == y.dtype and np.array_equal(x, y)


def test_engine_iterations_nest_the_program_calls(tiny, tmp_path):
    cfg, params = tiny
    prog = mpk.compile(cfg, B, S, backend="megakernel").bind(params)
    eng = ServingEngine(prog, chunk=4, page_size=8)
    eng.submit(Request(0, [3, 4, 5, 6, 7, 8], max_new_tokens=3))
    eng.submit(Request(1, [9, 10], max_new_tokens=3))
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
        eng.step()                                   # an idle poll
    recs = spans.recorded()
    by_index = {s.index: s for s in recs}
    steps = [s for s in recs if s.name == "mpk.engine.step"]
    its = [s for s in steps if s.attrs["kind"] != "idle"]
    kinds = [s.attrs["kind"] for s in its]
    assert "mixed" in kinds and "decode" in kinds
    assert steps[-1].attrs["kind"] == "idle"       # the poll, at least
    for it in steps:
        kids = [s.name for s in recs if s.parent == it.index]
        if it.attrs["kind"] == "idle":
            assert kids == ["mpk.engine.schedule"]
            assert it.attrs["running"] == 0
            continue
        call = "mpk.step" if it.attrs["kind"] == "decode" else "mpk.prefill"
        assert kids == ["mpk.engine.schedule", call, "mpk.engine.sample"]
        assert it.attrs["running"] >= 1
    assert [s.attrs["iteration"] for s in its] == \
        list(range(1, eng.iterations + 1))
    pre = [s for s in recs if s.name == "mpk.prefill"]
    assert pre and all(s.attrs["width"] in (1, 2, 4) for s in pre)
    for p in pre:
        kids = [s.name for s in recs if s.parent == p.index]
        assert kids == ["mpk.prefill.gather", "mpk.prefill.compute",
                        "mpk.prefill.scatter", "mpk.prefill.readback"]
        assert by_index[p.parent].name == "mpk.engine.step"
    assert eng.decode_iterations == kinds.count("decode")
    written = _xplane_spans(tmp_path)
    assert {"mpk.engine.step", "mpk.engine.schedule", "mpk.engine.sample",
            "mpk.prefill", "mpk.prefill.compute", "mpk.step"} <= set(written)
