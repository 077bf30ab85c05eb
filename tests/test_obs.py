"""Observability: the heap-resident trace ring, Chrome-trace export,
predicted-vs-observed reconciliation, and the unified metrics surfaces.

Acceptance contract (the in-kernel task timeline):

* ``trace=False`` is a no-op — descriptor table and logits are bitwise
  identical to a trace-enabled compile (the ring only ever APPENDS heap
  words after every existing region),
* the ring's global tick counter hands out each slot a strict
  [start, end) interval: the full tick stream is a permutation of
  0..2·slots−1,
* the decoded timeline is schema-valid Chrome-trace JSON and consistent
  with the descriptor event-counter semantics (every signaler of an
  event ends before any waiter starts),
* ``reconcile(predicted, observed)`` matches every compute task and the
  start-order (rank) skew stays bounded — the property that makes the
  compiler replay a trustworthy cost oracle.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import compile as mpk_compile
from repro.configs import get_config
from repro.models import init_params
from repro.obs import (check_event_order, chrome_trace, decode_ring,
                       predicted_task_trace, reconcile, sequential_trace,
                       validate_chrome_trace)

KEY = jax.random.PRNGKey(0)
TOKS = np.array([3, 7], np.int32)
LENS = np.zeros((2,), np.int32)


@pytest.fixture(scope="module")
def quickstart():
    cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                              n_layers=1)
    params = init_params(cfg, KEY, dtype=jnp.float32)
    return cfg, params


def _run(cfg, params, *, workers=2, trace=True, backend="megakernel"):
    prog = mpk_compile(cfg, 2, 16, backend=backend, num_workers=workers,
                       trace=trace).bind(params).init_state()
    if cfg.embed_input:
        toks = np.asarray(jax.random.normal(KEY, (2, cfg.d_model)),
                          np.float32)
    else:
        toks = TOKS
    logits = prog.step(toks, LENS)
    return prog, np.asarray(logits)


@pytest.fixture(scope="module")
def mk_static(quickstart):
    cfg, params = quickstart
    return _run(cfg, params, workers=2)


# ---------------------------------------------------------------------------
# Trace off = bitwise no-op.
# ---------------------------------------------------------------------------


def test_trace_off_bitwise_unchanged(quickstart, mk_static):
    """The ring is pure observation: with trace=False the descriptor
    table and the decoded logits are bitwise what they were before the
    ring existed (same compile, trace=True, gives identical ones too)."""
    cfg, params = quickstart
    prog_on, logits_on = mk_static
    prog_off, logits_off = _run(cfg, params, workers=2, trace=False)
    assert not prog_off.plan.trace and prog_on.plan.trace
    assert np.array_equal(prog_off.plan.descs, prog_on.plan.descs), \
        "trace=True must not perturb the descriptor table"
    assert np.array_equal(logits_off, logits_on), \
        "trace=True must not perturb the numerics"
    # the ring strictly APPENDS: it starts exactly where the untraced
    # heap ended, so every existing offset is unchanged
    assert prog_on.plan.ring_offset == prog_off.plan.heap_size


# ---------------------------------------------------------------------------
# Ring mechanics: tick permutation, schema, event order.
# ---------------------------------------------------------------------------


def test_tick_stream_is_a_permutation(mk_static):
    """The global fetch-and-increment clock hands every grid slot two
    distinct ticks; across the whole launch the raw ring's start/end
    words are exactly {0, ..., 2·slots−1} with start < end per slot."""
    prog, _ = mk_static
    ring = prog.executor.task_ring()
    ticks = np.concatenate([ring[:, 3], ring[:, 4]]).astype(np.int64)
    assert sorted(ticks.tolist()) == list(range(2 * ring.shape[0]))
    assert (ring[:, 3] < ring[:, 4]).all()


@pytest.mark.parametrize("workers", [1, 2])
def test_schema_order_and_reconcile(workers, quickstart, mk_static):
    cfg, params = quickstart
    prog, _ = mk_static if workers == 2 else _run(cfg, params,
                                                  workers=workers)
    observed = prog.trace()
    assert observed.origin == "kernel"
    assert observed.num_workers == workers

    obj = chrome_trace(observed)
    assert validate_chrome_trace(obj) == []
    assert validate_chrome_trace(json.dumps(obj)) == []
    names = {e.get("name") for e in obj["traceEvents"]
             if e.get("ph") == "X"}
    assert "matmul" in names and "attention_decode" in names

    assert check_event_order(observed) == []

    predicted = prog.predicted_trace()
    rep = reconcile(predicted, observed)
    assert rep.matched == rep.n_predicted == rep.n_observed
    assert rep.unmatched_predicted == [] and rep.unmatched_observed == []
    assert rep.mean_abs_rank_skew < 0.25
    # placement is a compile-time decision the kernel obeys
    assert rep.worker_agreement == 1.0


def test_reserved_words_stay_unset(mk_static):
    """The counter block's reserved words 8-11 stay zero and every ring
    record's reserved word 5 reads -1 after a launch."""
    from repro.kernels.megakernel.desc import STATS_WORDS

    prog, _ = mk_static
    plan = prog.plan
    heap = prog.executor.read_heap()
    stats = heap[plan.stats_offset:
                 plan.stats_offset + plan.num_workers * STATS_WORDS]
    assert (stats.reshape(plan.num_workers, STATS_WORDS)[:, 8:12]
            == 0).all()
    assert (prog.executor.task_ring()[:, 5] == -1).all()


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "gemma-7b", "qwen2-vl-2b",
                                  "granite-moe-1b-a400m"])
def test_trace_ring_per_family(arch):
    """The ring on other families' kinds (SSM and conv, GeGLU, M-RoPE
    with embedding input, MoE): the ticks are a permutation of
    0..2N-1, event order holds, and every record names its own grid
    slot and that slot's kind from the descriptor table."""
    cfg = dataclasses.replace(get_config(arch).reduced(), n_layers=1)
    params = init_params(cfg, KEY, dtype=jnp.float32)
    prog, _ = _run(cfg, params, workers=2)
    ring = prog.executor.task_ring()
    n = ring.shape[0]
    ticks = np.concatenate([ring[:, 3], ring[:, 4]]).astype(np.int64)
    assert sorted(ticks.tolist()) == list(range(2 * n))
    assert check_event_order(prog.trace()) == []
    assert (ring[:, 1] == np.arange(n)).all()
    assert (ring[:, 2] == prog.plan.descs[:, 0]).all()


def test_compile_takes_no_scheduler(quickstart):
    cfg, _ = quickstart
    with pytest.raises(TypeError):
        mpk_compile(cfg, 2, 16, backend="megakernel",
                    **{"scheduler": "static"})


@pytest.mark.slow
@pytest.mark.parametrize("workers", [1, 2, 4])
def test_sweep_widths(quickstart, workers):
    """The acceptance sweep at W ∈ {1, 2, 4}: schema-valid export, event
    order intact, every task reconciled."""
    cfg, params = quickstart
    prog, _ = _run(cfg, params, workers=workers)
    observed = prog.trace()
    assert validate_chrome_trace(chrome_trace(observed)) == []
    assert check_event_order(observed) == []
    rep = reconcile(prog.predicted_trace(), observed)
    assert rep.matched == rep.n_predicted == rep.n_observed
    assert rep.mean_abs_rank_skew < 0.25


# ---------------------------------------------------------------------------
# The other producers: interpreter timeline, predicted timeline.
# ---------------------------------------------------------------------------


def test_interpreter_trace_reconciles(quickstart):
    cfg, params = quickstart
    prog, _ = _run(cfg, params, workers=2, backend="interpreter")
    tl = prog.trace()
    assert tl.origin == "interpreter"
    rep = reconcile(prog.predicted_trace(), tl)
    assert rep.matched == rep.n_predicted
    assert rep.unmatched_observed == []


def test_untraced_kernel_raises(quickstart):
    cfg, params = quickstart
    prog, _ = _run(cfg, params, workers=1, trace=False)
    with pytest.raises(ValueError, match="trace=True"):
        prog.trace()


def test_predicted_trace_makespan_matches_sim(quickstart):
    """predicted_task_trace charges exactly the simulate() costs: the
    trace's makespan equals the replayed partition makespan."""
    from repro.core.runtime_sim import SimConfig, simulate

    cfg, params = quickstart
    prog = mpk_compile(cfg, 2, 16, backend="interpreter", num_workers=2)
    tl = predicted_task_trace(prog.compiled, num_workers=2)
    sim = simulate(prog.compiled, SimConfig(mode="mpk", n_workers=2))
    assert tl.meta["makespan"] == pytest.approx(sim.makespan)
    assert tl.makespan == pytest.approx(sim.makespan)
    assert len({e.task for e in tl.events}) == len(tl.events)


def test_sequential_trace_static(quickstart):
    cfg, params = quickstart
    prog = mpk_compile(cfg, 2, 16, backend="interpreter", num_workers=2)
    tl = sequential_trace(prog.compiled)
    assert len(tl.events) == len(prog.compiled.order)
    assert [e.start for e in tl.events] == \
        [2.0 * i for i in range(len(tl.events))]


# ---------------------------------------------------------------------------
# Satellite: the 2^20 f32 counter spill encoding round-trips exactly.
# ---------------------------------------------------------------------------


def test_stats_spill_roundtrip_past_f32_precision():
    """row_copies and state_row_copies counts past 2^24 (f32 integer
    precision) round-trip exactly through the (low, spill) pair the
    kernel maintains for each."""
    from repro.kernels.megakernel.ops import (ROW_SPILL_UNIT, SPILL_WORDS,
                                              STATS_FIELDS, STATS_WORDS,
                                              decode_stats_row)

    assert ROW_SPILL_UNIT == 1 << 20
    assert SPILL_WORDS == {"row_copies": 4, "state_row_copies": 13}
    for value in [0, 1, (1 << 20) - 1, 1 << 20, (1 << 24) + 1,
                  5 * (1 << 24) + 12345, (1 << 40) + 987654]:
        low, spill = value % ROW_SPILL_UNIT, value // ROW_SPILL_UNIT
        # both halves must be f32-exact for the decode to be exact
        assert int(np.float32(low)) == low
        assert int(np.float32(spill)) == spill
        for name, spill_word in SPILL_WORDS.items():
            v = np.zeros((STATS_WORDS,), np.float32)
            v[STATS_FIELDS[name]] = low
            v[spill_word] = spill
            assert decode_stats_row(v)[name] == value


def test_read_stats_block_named_fields():
    from repro.kernels.megakernel.ops import (STATS_FIELDS, STATS_WORDS,
                                              read_stats_block)

    heap = np.zeros((7 + 2 * STATS_WORDS,), np.float32)
    for w in range(2):
        for name, i in STATS_FIELDS.items():
            heap[7 + w * STATS_WORDS + i] = 100 * w + i
    rows = read_stats_block(heap, 7, 2)
    assert len(rows) == 2
    for w, row in enumerate(rows):
        for name, i in STATS_FIELDS.items():
            assert row[name] == 100 * w + i, name


def test_kernel_counters_use_shared_reader(mk_static):
    """The executor's worker_counters is the shared read_stats_block."""
    from repro.kernels.megakernel.ops import read_stats_block

    prog, _ = mk_static
    ex = prog.executor
    direct = read_stats_block(ex._heap, prog.plan.stats_offset,
                              prog.plan.num_workers)
    assert ex.worker_counters() == direct


# ---------------------------------------------------------------------------
# Unified metrics snapshot.
# ---------------------------------------------------------------------------


def test_metrics_snapshot_json_roundtrip(mk_static):
    prog, _ = mk_static
    snap = prog.metrics_snapshot()
    again = json.loads(json.dumps(snap))
    assert set(again) >= {"program", "compiler", "pipeline", "workers",
                          "step_count"}
    assert again["step_count"] == prog.step_count
    assert again["workers"]["event_wait_violations"] == 0


def test_engine_metrics_snapshot(quickstart):
    from repro.runtime import Request, ServingEngine

    cfg, params = quickstart
    eng = ServingEngine.from_model(cfg, params, max_slots=2, max_seq=32)
    eng.submit(Request(0, [3, 5, 7], max_new_tokens=3))
    eng.run()
    snap = eng.metrics_snapshot()
    json.dumps(snap)
    assert snap["serving"]["n_finished"] == 1.0
    assert "ttft_mean_s" in snap["serving"]


# ---------------------------------------------------------------------------
# Satellite: RequestMetrics edge cases.
# ---------------------------------------------------------------------------


def test_request_metrics_edge_cases():
    from repro.runtime import RequestMetrics

    m = RequestMetrics(arrival_s=1.0)
    # no milestone reached yet: everything is None, never garbage
    assert m.ttft_s is None and m.queue_s is None
    assert m.tpot_s(10) is None
    m.first_sched_s = 1.5
    assert m.queue_s == pytest.approx(0.5)
    assert m.ttft_s is None          # scheduled but no token yet
    m.first_token_s = 2.0
    assert m.ttft_s == pytest.approx(1.0)
    assert m.tpot_s(10) is None      # not finished yet
    m.finish_s = 4.0
    # a 1-token request has no decode phase: TPOT undefined, not 0/0
    assert m.tpot_s(0) is None and m.tpot_s(1) is None
    assert m.tpot_s(5) == pytest.approx(0.5)


def test_metrics_survive_preemption_and_readmission(quickstart):
    """An evicted request keeps its FIRST-schedule milestone (queue time
    measures time-to-first-service, not time-to-last-admission) and its
    latency metrics stay well-defined across the evict/re-admit cycle."""
    from repro.runtime import Request, ServingEngine

    cfg, params = quickstart
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(1, cfg.vocab, size=20).tolist(),
                    max_new_tokens=8) for i in range(5)]
    eng = ServingEngine.from_model(cfg, params, max_slots=3, max_seq=32,
                                   page_size=8, chunk=8, total_pages=6)
    first_sched: dict = {}
    orig_admit = eng.kv.admit

    def admit_spy(rid, n):
        slot = orig_admit(rid, n)
        first_sched.setdefault(rid, eng._ticks)
        return slot

    eng.kv.admit = admit_spy
    for r in reqs:
        eng.submit(r)
    done = {r.request_id: r for r in eng.run()}
    preempted = [r for r in done.values() if r.metrics.n_preemptions > 0]
    assert preempted, "page pressure must force at least one preemption"
    summary = eng.metrics_summary()
    assert summary["preemptions"] == float(
        sum(r.metrics.n_preemptions for r in done.values()))
    for r in done.values():
        m = r.metrics
        # milestones are monotone and never reset by re-admission
        assert m.queue_s is not None and m.queue_s >= 0
        assert m.ttft_s is not None and m.ttft_s >= m.queue_s
        assert m.finish_s >= m.first_token_s >= m.first_sched_s
        assert m.tpot_s(len(r.output)) > 0
    snap = eng.metrics_snapshot()
    json.dumps(snap)
    assert snap["serving"]["preemptions"] == summary["preemptions"]
