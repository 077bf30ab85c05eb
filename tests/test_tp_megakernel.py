"""The multi-chip COMM-task subsystem: chunked ring-allreduce planner,
per-chip task-table stamping, in-kernel execution, and the mpk_tp
simulator — all pinned to the SAME ``expand_ring_allreduce`` schedule.

Fast lane: the ring-protocol oracle, the stamping invariants, the
simulator reduction, the committed BENCH_tp.json certification and one
TP=2 kernel-parity smoke.  The TP∈{1,2,4} dense+MoE kernel sweeps are
slow-marked.
"""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.comm_tasks import (expand_ring_allreduce,
                                          n_comm_events, n_ring_steps,
                                          ref_ring_allreduce, ring_chunks,
                                          ring_duration,
                                          serialized_duration)
from repro.kernels.megakernel.desc import AR_CHUNK_CODE, REMOTE_COPY_CODE

KEY = jax.random.PRNGKey(7)
BENCH_TP = Path(__file__).resolve().parent.parent / "benchmarks" \
    / "BENCH_tp.json"


# ---------------------------------------------------------------- planner

@pytest.mark.parametrize("n_chips", [1, 2, 3, 4, 8])
def test_ring_plan_invariants(n_chips):
    """Shape of the expansion: C tasks per relative step, every receive's
    matching send at a strictly earlier step, chunks tile the span."""
    span = 1001
    tasks = expand_ring_allreduce(span, n_chips)
    assert len(tasks) == n_chips * n_ring_steps(n_chips)
    chunks = ring_chunks(span, n_chips)
    assert sum(l for _, l in chunks) == span
    send_step = {}
    for t in tasks:
        if t.kind == "send":
            send_step[t.sig_ev] = t.step
    for t in tasks:
        if t.kind == "recv":
            assert send_step[t.wait_ev] < t.step, t
    # every comm event is signalled exactly once and waited exactly once
    waits = [t.wait_ev for t in tasks if t.kind == "recv"]
    assert sorted(waits) == sorted(send_step) == \
        list(range(n_comm_events(n_chips)))


@pytest.mark.parametrize("n_chips", [1, 2, 4, 5])
@pytest.mark.parametrize("span", [7, 64, 4096])
def test_ring_reference_is_exact(n_chips, span):
    """The protocol oracle: replicated inputs come out bitwise unchanged
    (owner-masked partials make x+0.0 exact); distinct inputs resolve to
    each chunk's owner value on every chip."""
    rng = np.random.default_rng(span * 31 + n_chips)
    x = rng.standard_normal(span).astype(np.float32)
    outs = ref_ring_allreduce([x.copy() for _ in range(n_chips)])
    for o in outs:
        assert np.array_equal(o, x)
    shards = [rng.standard_normal(span).astype(np.float32)
              for _ in range(n_chips)]
    outs = ref_ring_allreduce(shards)
    want = np.empty(span, np.float32)
    for j, (st, ln) in enumerate(ring_chunks(span, n_chips)):
        want[st:st + ln] = shards[j][st:st + ln]
    for o in outs:
        assert np.array_equal(o, want)


def test_ring_cost_model():
    """Bandwidth regime: the ring moves 2(C-1)/C of the serialized
    bytes, so large spans win at any C; C=2 wins at every span (equal
    round count).  Degenerate C=1: both collapse."""
    assert ring_duration(10, 1) < serialized_duration(10, 2)
    assert serialized_duration(10, 1) == 0.0
    for span in (64, 10_000, 1_000_000):
        assert ring_duration(span, 2) < serialized_duration(span, 2)
    big = 1_000_000
    assert ring_duration(big, 4) < serialized_duration(big, 4)


# ---------------------------------------------------------------- stamping

def _plan(cfg, b=2, s=16, **kw):
    from repro.kernels.megakernel.ops import compile_decode_megakernel
    return compile_decode_megakernel(cfg, b, s, **kw)


def _quick_cfg(arch="deepseek-7b", layers=1):
    return dataclasses.replace(get_config(arch).reduced(),
                               n_layers=layers)


def test_stamped_table_matches_ring_closed_forms():
    """desc/kernel lockstep: the stamped COMM descriptor counts equal the
    ``expand_ring_allreduce`` closed forms, per-chip worker lanes grow
    to C·W, and the heap carries C mirrored regions + the global event
    table."""
    cfg = _quick_cfg()
    for tp in (2, 4):
        p = _plan(cfg, tp=tp)
        C = p.n_chips
        assert C == tp and p.chip_stride > 0
        assert p.num_workers == C * 1        # compile-time W was 1
        kinds = p.descs[:, 0]
        n_coll = int(np.sum((kinds == AR_CHUNK_CODE)
                            & (p.descs[:, 14] == 0))) // C
        assert n_coll > 0
        assert int(np.sum(kinds == REMOTE_COPY_CODE)) == \
            n_coll * C * 2 * (C - 1)
        assert int(np.sum(kinds == AR_CHUNK_CODE)) == \
            n_coll * C * (1 + 2 * (C - 1))
        # the event table = C mirrored single-chip tables + the ring's
        # cross-chip counters, sitting right past the chip regions
        nev0 = (p.num_events - n_coll * n_comm_events(C)) / C
        assert nev0 == int(nev0) and nev0 >= 0
        assert p.event_offset == C * p.chip_stride
        # every send's peer staging target lies beyond the event table
        sends = p.descs[kinds == REMOTE_COPY_CODE]
        if len(sends):
            assert int(sends[:, 4].min()) >= \
                p.event_offset + p.num_events


def test_api_tp_validation():
    from repro import api
    with pytest.raises(ValueError):
        api.compile(_quick_cfg(), 2, 16, backend="jax", tp=2)


# ------------------------------------------------------------ kernel runs

def _run(cfg, tp, binds, **kw):
    from repro.kernels.megakernel import MegakernelExecutor
    p = _plan(cfg, tp=tp, **kw)
    ex = MegakernelExecutor(p, cfg)
    out = ex.run_once(binds)
    assert ex.pipeline_counters()["event_wait_violations"] == 0
    return p, ex, out


def _bindings(cfg, b=2, s=16):
    from repro.core.lowering import decode_bindings
    from repro.models import init_cache, init_params
    params = jax.tree.map(np.asarray,
                          init_params(cfg, KEY, dtype=jnp.float32))
    cache = jax.tree.map(np.asarray,
                         init_cache(cfg, b, s, dtype=jnp.float32))
    inp = (np.asarray(jax.random.normal(KEY, (b, cfg.d_model))) * 0.1
           if cfg.embed_input else np.array([3, 7]))
    return decode_bindings(cfg, params, cache, inp,
                           np.array([1, 4], np.int32))


def test_tp2_kernel_bitwise_parity_smoke():
    """Fast lane: the TP=2 stamped megakernel produces bitwise-identical
    logits to TP=1, and both chips hold identical output copies."""
    cfg = _quick_cfg()
    binds = _bindings(cfg)
    _, _, o1 = _run(cfg, 1, binds)
    p2, ex2, o2 = _run(cfg, 2, binds)
    assert np.array_equal(o1["logits"], o2["logits"])
    heap = ex2.read_heap()
    assert np.array_equal(p2.read_output(heap, "logits", chip=0),
                          p2.read_output(heap, "logits", chip=1))


def test_tp2_chip_isolation():
    """The per-chip heap regions are really disjoint: corrupting chip
    1's weight region after upload changes chip 1's logits only — a
    silent-aliasing guard on the fused transport."""
    from repro.kernels.megakernel import MegakernelExecutor
    cfg = _quick_cfg()
    binds = _bindings(cfg)
    p = _plan(cfg, tp=2)
    ex = MegakernelExecutor(p, cfg)
    heap0 = p.build_heap(binds)
    ex.upload(heap0)
    lens = np.asarray(binds["seq_lens"], np.int32)
    tok = binds["h0"] if cfg.embed_input else binds["tokens"]
    clean = ex.step(tok, lens)
    # corrupt one weight slot inside chip 1's mirror only
    wname = next(n for n in p.layout
                 if n.endswith("wq") or n.endswith("wi"))
    slot = p.layout[wname]
    heap1 = heap0.copy()
    lo = slot.offset + p.chip_stride
    heap1[lo:lo + slot.rows * slot.ld] *= 1.0009765625  # exact in f32
    ex.upload(heap1)
    ex.step(tok, lens)
    heap = ex.read_heap()
    c0 = p.read_output(heap, "logits", chip=0)
    c1 = p.read_output(heap, "logits", chip=1)
    # chip 1's corrupted compute feeds its owned ring chunks, so both
    # chips' outputs shift away from the clean run — proving chip 1's
    # region is really chip 1's (no silent aliasing onto chip 0) —
    # while the ring still converges the two chips to the same values
    assert not np.array_equal(np.asarray(clean), c1)
    assert not np.array_equal(np.asarray(clean), c0)
    assert np.array_equal(c0, c1)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["deepseek-7b", "granite-moe-1b-a400m"])
def test_tp_sweep_bitwise_parity(arch):
    """TP ∈ {1, 2, 4} on dense + MoE quickstart configs: bitwise parity
    against TP=1 and across chips (the ISSUE acceptance sweep)."""
    cfg = _quick_cfg(arch)
    binds = _bindings(cfg)
    _, _, ref = _run(cfg, 1, binds)
    for tp in (2, 4):
        p, ex, out = _run(cfg, tp, binds)
        assert np.array_equal(ref["logits"], out["logits"]), (arch, tp)
        heap = ex.read_heap()
        c0 = p.read_output(heap, "logits", chip=0)
        for c in range(1, p.n_chips):
            assert np.array_equal(
                c0, p.read_output(heap, "logits", chip=c)), (arch, tp, c)


@pytest.mark.slow
def test_tp2_multiworker_parity():
    cfg = _quick_cfg()
    binds = _bindings(cfg)
    _, _, ref = _run(cfg, 1, binds)
    _, _, out = _run(cfg, 2, binds, num_workers=2)
    assert np.array_equal(ref["logits"], out["logits"])


# --------------------------------------------------------------- simulator

def _compiled(cfg, tp, b=2, s=16, W=1):
    from repro.core.compile import CompileOptions, megakernelize
    from repro.core.decompose import DecomposeConfig
    from repro.core.lowering import build_decode_graph
    return megakernelize(
        build_decode_graph(cfg, b, s, tp=tp),
        CompileOptions(num_workers=W, decompose=DecomposeConfig(max_rows=8)))


def test_mpk_tp_reduces_exactly_to_static_replay():
    """Acceptance: ``simulate("mpk_tp")`` at W=1, TP=1 is the existing
    static replay, bit for bit."""
    from repro.core.runtime_sim import SimConfig, simulate
    c = _compiled(_quick_cfg(layers=2), tp=1)
    a = simulate(c, SimConfig(mode="mpk", n_workers=1))
    b = simulate(c, SimConfig(mode="mpk_tp", tp=1, n_workers=1))
    assert a.makespan == b.makespan and a.busy_frac == b.busy_frac
    assert a.worker_busy == b.worker_busy


def test_mpk_tp_charges_ring_rounds():
    """tp>1: collectives cost exactly ``ring_duration`` of their span
    (the same schedule the kernel executes), and the serialized plan
    costs ``serialized_duration``."""
    from repro.core.runtime_sim import SimConfig, simulate
    c = _compiled(_quick_cfg(), tp=2, W=1)
    tg = c.tg
    comm = [t for t in tg.tasks.values() if t.is_comm and not t.is_dummy]
    assert comm, "tp=2 graph must contain allreduce tasks"
    ring = simulate(c, SimConfig(mode="mpk_tp", tp=2, n_workers=1,
                                 overlap_comm=False))
    ser = simulate(c, SimConfig(mode="mpk_tp", tp=2, n_workers=1,
                                comm_plan="serialized",
                                overlap_comm=False))
    base = simulate(c, SimConfig(mode="mpk", n_workers=1,
                                 overlap_comm=False))
    d_ring = sum(ring_duration(int(t.bytes_moved() // 4), 2)
                 for t in comm)
    d_ser = sum(serialized_duration(int(t.bytes_moved() // 4), 2)
                for t in comm)
    d_base = sum(t.bytes_moved() / SimConfig().ici_bw + 2.0e-6
                 for t in comm)
    assert ring.makespan == pytest.approx(
        base.makespan - d_base + d_ring, rel=1e-9)
    assert ser.makespan == pytest.approx(
        base.makespan - d_base + d_ser, rel=1e-9)
    # C=2 ring strictly beats the serialized whole-tensor baseline
    assert ring.makespan < ser.makespan


# ---------------------------------------------------------- committed JSON

def test_committed_bench_tp_certifies_acceptance():
    """The committed BENCH_tp.json keeps certifying the ISSUE acceptance:
    bitwise kernel parity at TP∈{1,2,4}, zero event-wait violations, and
    the chunked ring beating the serialized allreduce at every TP."""
    doc = json.loads(BENCH_TP.read_text())
    for tp, rec in doc["fig11"]["kernel"].items():
        assert rec["bitwise_equal_tp1"] is True, tp
        assert rec["chips_bitwise_equal"] is True, tp
        assert rec["event_wait_violations"] == 0, tp
    assert set(doc["fig11"]["kernel"]) >= {"tp1", "tp2", "tp4"}
    for tp, rec in doc["fig13"]["ring_vs_serialized"].items():
        assert rec["ring_win"] > 1.0, (tp, rec)
    k = doc["fig13"]["kernel"]
    assert k["bitwise_equal_tp1"] is True
    assert k["event_wait_violations"] == 0
