"""Serving engine: continuous batching must not change results — each
request's greedy output equals its isolated single-request output, under
staggered admissions and slot reuse."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import init_params
from repro.runtime import PagedKVCache, Request, ServingEngine

KEY = jax.random.PRNGKey(0)


def _make(cfg_name="deepseek-7b"):
    cfg = get_config(cfg_name).reduced()
    params = init_params(cfg, KEY, dtype=jnp.float32)
    return cfg, params


def test_batched_equals_isolated():
    cfg, params = _make()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=5).tolist() for _ in range(5)]

    # isolated: one engine per request
    isolated = []
    for i, p in enumerate(prompts):
        eng = ServingEngine.from_model(cfg, params, max_slots=1, max_seq=32)
        eng.submit(Request(i, p, max_new_tokens=6))
        isolated.append(eng.run()[0].output)

    # batched with fewer slots than requests (forces queueing + reuse)
    eng = ServingEngine.from_model(cfg, params, max_slots=2, max_seq=32)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new_tokens=6))
    done = {r.request_id: r.output for r in eng.run()}
    for i in range(5):
        assert done[i] == isolated[i], f"request {i} diverged under batching"


def test_paged_kv_accounting():
    kv = PagedKVCache(n_slots=4, max_seq=64, page_size=16)
    assert kv.total_pages == 16
    kv.admit(10, 17)   # 2 pages
    kv.admit(11, 1)    # 1 page
    assert kv.used_pages == 3
    assert kv.free_pages == 13
    kv.advance(11)
    kv.release(10)
    assert kv.used_pages == 1
    assert kv.seq_lens().count(0) == 3


def test_slot_reuse_after_release():
    kv = PagedKVCache(n_slots=1, max_seq=16, page_size=4)
    s0 = kv.admit(1, 4)
    kv.release(1)
    s1 = kv.admit(2, 4)
    assert s0 == s1


def test_program_step_cache_keyed_by_cfg_and_width():
    """Jitted prefill steps specialize on (config, chunk width) — a
    shared cache can never hand one model's compiled step to another
    program/engine."""
    cfg, params = _make()
    shared = {}
    eng = ServingEngine.from_model(cfg, params, max_slots=2, max_seq=32,
                                   step_cache=shared)
    prog = eng.program
    f1, f2, f1b = (prog._prefill_fn(1), prog._prefill_fn(2),
                   prog._prefill_fn(1))
    assert f1 is f1b and f1 is not f2
    assert set(shared) == {(cfg, 1), (cfg, 2)}
    cfg2, params2 = _make("gemma-7b")
    eng2 = ServingEngine.from_model(cfg2, params2, max_slots=2, max_seq=32,
                                    step_cache=shared)
    assert eng2.program._prefill_fn(1) is not f1


def test_submit_rejects_oversized_request():
    cfg, params = _make()
    eng = ServingEngine.from_model(cfg, params, max_slots=1, max_seq=16,
                        page_size=16)
    with np.testing.assert_raises(ValueError):
        eng.submit(Request(0, list(range(1, 13)), max_new_tokens=8))


def test_poisson_workload_prompt_length_range():
    from repro.launch.serve import poisson_workload

    reqs = poisson_workload(np.random.default_rng(0), 32, 4, 3, 100, 0.0,
                            prompt_len_max=9)
    lens = {len(r.prompt) for r in reqs}
    assert lens <= set(range(4, 10)) and len(lens) > 1
    assert all(r.arrival_time == 0.0 and r.max_new_tokens == 3
               for r in reqs)
    fixed = poisson_workload(np.random.default_rng(0), 4, 6, 3, 100, 0.0)
    assert {len(r.prompt) for r in fixed} == {6}


def test_compile_cache_dir(monkeypatch):
    """Entry points keep the compile cache where ``JAX_COMPILATION_CACHE_
    DIR`` says, and otherwise at the fixed ``<repo>/.jax_cache``."""
    import os

    from repro.launch.compile_cache import REPO_ROOT, use_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = use_compile_cache()
        assert path == os.path.join(str(REPO_ROOT), ".jax_cache")
        assert (REPO_ROOT / "chip_smoke.py").is_file()   # the checkout
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
