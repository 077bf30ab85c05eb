"""``mpk.Program``: one compile-once / step-many API over all three
execution backends — parity, persistence and engine integration.

The acceptance contract: a ``Program("megakernel")`` performs exactly one
``make_megakernel``/jit trace and one full weight upload across a
16-step decode loop (per-step host work is the partial input-heap
update), its logits stay parity-matched with the ``"jax"`` backend on
dense, MoE and SSM architectures, and ``ServingEngine`` runs end-to-end
on a Program of any backend.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpk
from repro.configs import get_config
from repro.kernels.megakernel import kernel as mk_kernel
from repro.models import init_params
from repro.runtime import Request, ServingEngine

KEY = jax.random.PRNGKey(0)

#: one config per family named in the acceptance criteria
FAMILIES = ["deepseek-7b",            # dense
            "granite-moe-1b-a400m",   # MoE
            "mamba2-2.7b"]            # SSM


def _cfg(arch, layers=1):
    return dataclasses.replace(get_config(arch).reduced(), n_layers=layers)


def _params(cfg):
    return init_params(cfg, KEY, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Compile-once / step-many: the megakernel backend is genuinely persistent.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", FAMILIES)
def test_megakernel_compile_once_16_step_parity(arch):
    """≥16-step decode loop: exactly ONE make_megakernel, ONE jit trace,
    ONE full weight upload — and logits parity with the jax oracle at
    every step (state carried in the device-resident heap)."""
    cfg = _cfg(arch)
    params = _params(cfg)
    b, s = 2, 24
    makes0 = mk_kernel.make_count()
    prog = mpk.compile(cfg, b, s, backend="megakernel")
    oracle = mpk.compile(cfg, b, s, backend="jax")
    prog.bind(params).init_state()
    oracle.bind(params).init_state()
    assert mk_kernel.make_count() - makes0 == 1

    rng = np.random.default_rng(0)
    lens = np.zeros((b,), np.int32)
    for i in range(16):
        toks = rng.integers(1, cfg.vocab, size=b).astype(np.int32)
        got = prog.step(toks, lens)
        ref = oracle.step(toks, lens)
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4,
                                   err_msg=f"step {i}")
        lens += 1
    assert prog.step_count == 16
    # the compile-once / upload-once contract, via the hooks
    assert mk_kernel.make_count() - makes0 == 1
    assert prog.trace_count == 1
    assert prog.upload_count == 1


def test_interpreter_matches_jax_multi_step():
    """Interpreter backend: multi-step state carry (prefill + decode)
    tracks the oracle."""
    cfg = _cfg("deepseek-7b", layers=2)
    params = _params(cfg)
    b, s = 2, 32
    progs = [mpk.compile(cfg, b, s, backend=bk).bind(params).init_state()
             for bk in ("jax", "interpreter")]
    rng = np.random.default_rng(1)
    chunk = rng.integers(1, cfg.vocab, size=(b, 4)).astype(np.int32)
    lens = np.zeros((b,), np.int32)
    pre = [p.prefill(chunk, lens, np.array([4, 2], np.int32))
           for p in progs]
    np.testing.assert_allclose(pre[1], pre[0], rtol=2e-4, atol=2e-4)
    lens = np.array([4, 2], np.int32)
    for _ in range(3):
        toks = rng.integers(1, cfg.vocab, size=b).astype(np.int32)
        outs = [p.step(toks, lens) for p in progs]
        np.testing.assert_allclose(outs[1], outs[0], rtol=2e-4, atol=2e-4)
        lens += 1


def test_backend_parity_hypothesis():
    """Property: for randomly drawn reduced configs / batches / length
    states, one decode step agrees between the jax oracle and the
    interpreter backend (the megakernel loop above covers pallas)."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    shared: dict = {}

    @settings(max_examples=5, deadline=None)
    @given(st.sampled_from(FAMILIES), st.integers(1, 3),
           st.integers(0, 10**9))
    def check(arch, batch, seed):
        cfg = _cfg(arch)
        params = _params(cfg)
        rng = np.random.default_rng(seed)
        progs = [mpk.compile(cfg, batch, 16, backend=bk,
                             step_cache=shared).bind(params).init_state()
                 for bk in ("jax", "interpreter")]
        lens = rng.integers(0, 8, size=batch).astype(np.int32)
        for _ in range(2):
            toks = rng.integers(1, cfg.vocab, size=batch).astype(np.int32)
            outs = [p.step(toks, lens) for p in progs]
            np.testing.assert_allclose(outs[1], outs[0],
                                       rtol=3e-4, atol=3e-4)
            lens += 1

    check()


def test_reset_slot_isolates_requests():
    """reset_slot must zero one slot's state without disturbing others
    (slot reuse in the engine) — checked on the stateful SSM family."""
    cfg = _cfg("mamba2-2.7b")
    params = _params(cfg)
    b, s = 2, 16
    rng = np.random.default_rng(3)
    toks = rng.integers(1, cfg.vocab, size=(4, b)).astype(np.int32)

    prog = mpk.compile(cfg, b, s, backend="megakernel")
    prog.bind(params).init_state()
    lens = np.zeros((b,), np.int32)
    for i in range(3):          # pollute both slots' SSM/conv state
        prog.step(toks[i], lens)
        lens += 1
    prog.reset_slot(0)
    got = prog.step(toks[3], np.array([0, lens[1]], np.int32))

    fresh = mpk.compile(cfg, b, s, backend="jax").bind(params).init_state()
    flens = np.zeros((b,), np.int32)
    for i in range(3):          # slot 1's history only
        fresh.step(np.stack([toks[i][1], toks[i][1]]), flens)
        flens += 1
    fresh.reset_slot(0)
    ref = fresh.step(toks[3], np.array([0, flens[1]], np.int32))
    np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# Engine integration: any backend serves end-to-end.
# ---------------------------------------------------------------------------


def _engine_streams(cfg, params, backend, prompts):
    prog = mpk.compile(cfg, 2, 32, backend=backend).bind(params)
    eng = ServingEngine(prog, chunk=8)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new_tokens=4))
    done = {r.request_id: r.output for r in eng.run()}
    return done, eng


def test_engine_runs_on_interpreter_backend():
    cfg = get_config("deepseek-7b").reduced()
    params = _params(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=5).tolist()
               for _ in range(3)]
    ref, _ = _engine_streams(cfg, params, "jax", prompts)
    got, eng = _engine_streams(cfg, params, "interpreter", prompts)
    assert got == ref
    assert eng.decode_iterations > 0  # decode went through the backend


@pytest.mark.slow
def test_engine_runs_on_megakernel_backend():
    """End-to-end serving on the persistent megakernel: identical greedy
    streams, pure-decode iterations inside the kernel, one jit trace."""
    cfg = get_config("deepseek-7b").reduced()
    params = _params(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=5).tolist()
               for _ in range(3)]
    ref, _ = _engine_streams(cfg, params, "jax", prompts)
    got, eng = _engine_streams(cfg, params, "megakernel", prompts)
    assert got == ref
    assert eng.decode_iterations > 0
    assert eng.program.trace_count == 1
    # weights moved once at bind; prefill restores state via partial
    # scatters, never a full re-upload
    assert eng.program.upload_count == 1
    assert eng.program.executor.state_scatter_count > 0


# ---------------------------------------------------------------------------
# API surface.
# ---------------------------------------------------------------------------


def test_empty_prompt_rejected_at_submit():
    """An empty prompt has no position to sample from; pre-validation
    beats the old engine's crash/livelock on the degenerate input."""
    cfg = get_config("deepseek-7b").reduced()
    eng = ServingEngine(
        mpk.compile(cfg, 1, 32, backend="jax").bind(_params(cfg)))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(0, [], max_new_tokens=2))


def test_unknown_backend_rejected():
    cfg = _cfg("deepseek-7b")
    with pytest.raises(ValueError):
        mpk.compile(cfg, 1, 8, backend="cuda")


def test_describe_and_stats():
    cfg = _cfg("deepseek-7b")
    prog = mpk.compile(cfg, 2, 16, backend="interpreter")
    d = prog.describe()
    assert d["backend"] == "interpreter"
    assert d["tasks"] > 0 and d["events"] > 0
    assert prog.stats["workspace_reuse_x"] >= 1.0


def test_workspace_liveness_reuse():
    """The liveness allocator shrinks the workspace and never overlaps
    two live tensors."""
    from repro.core.compile import megakernelize
    from repro.core.lowering import build_decode_graph

    cfg = get_config("deepseek-7b").reduced()
    g = build_decode_graph(cfg, 2, 32)
    c = megakernelize(g)
    s = c.stats
    assert s["workspace_elements"] < s["workspace_elements_no_reuse"]
    assert s["workspace_reuse_x"] > 1.0

    # recompute live ranges and assert spatial-temporal disjointness
    op_first, op_last = {}, {}
    for pos, tid in enumerate(c.lin.order):
        oid = c.tg.tasks[tid].op_id
        if oid < 0:
            continue
        op_first.setdefault(oid, pos)
        op_last[oid] = pos
    inf = len(c.lin.order) + 1
    outs = set(g.outputs)

    def rng(n):
        prod = g.producer.get(n)
        start = op_first.get(prod, 0) if prod is not None else 0
        if n in outs:
            return start, inf
        end = op_last.get(prod, start) if prod is not None else start
        for cons in g.consumers.get(n, ()):
            end = max(end, op_last.get(cons, start))
        return start, end

    items = sorted(
        ((c.workspace_layout[n][0], c.workspace_layout[n][1], *rng(n))
         for n in c.workspace_layout))
    for i, (o1, s1, a1, b1) in enumerate(items):
        for o2, s2, a2, b2 in items[i + 1:]:
            if o2 >= o1 + s1:
                break  # sorted by offset: no further spatial overlap
            assert not (a1 <= b2 and a2 <= b1), "live tensors overlap"


@pytest.mark.parametrize("backend", ["interpreter", "megakernel"])
def test_decode_parity_against_f32_reference(backend):
    """``api.parity.decode_parity`` — the check ``chip_smoke.py`` and
    ``mpk-serve --crosscheck`` run — from a prefilled state: the program
    decodes teacher-forced tokens within ``PARITY_TOL`` of the jax
    reference, and advances by exactly those steps."""
    from repro.api.parity import PARITY_TOL, decode_parity

    cfg = _cfg("deepseek-7b", layers=2)
    params = _params(cfg)
    prog = mpk.compile(cfg, 3, 24, backend=backend).bind(params).init_state()
    rng = np.random.default_rng(0)
    lens = np.array([4, 1, 3], np.int32)
    prog.prefill(rng.integers(1, cfg.vocab, size=(3, 4)).astype(np.int32),
                 np.zeros(3, np.int32), lens)
    ref = mpk.compile(cfg, 3, 24, backend="jax").bind(params)
    toks = rng.integers(1, cfg.vocab, size=(3, 3)).astype(np.int32)
    steps0 = prog.step_count
    errs = decode_parity(prog, ref, toks, lens)
    assert prog.step_count == steps0 + len(toks)
    assert set(errs) == {"highest", "default"}
    assert 0.0 <= errs["highest"] <= PARITY_TOL
