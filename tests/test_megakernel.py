"""The megakernel: one pallas_call executes an entire decode step and
matches both the tGraph interpreter and the JAX model oracle, for every
architecture family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.interpreter import execute_reference
from repro.core.lowering import decode_bindings
from repro.kernels.megakernel import MegakernelExecutor
from repro.kernels.megakernel.desc import DESC_WORDS
from repro.kernels.megakernel.ops import compile_decode_megakernel
from repro.models import init_cache, init_params, serve_step

KEY = jax.random.PRNGKey(5)

FAMILIES = [
    ("deepseek-7b", 2),              # dense (2 layers)
    ("gemma-7b", 1),                 # GeGLU + gemma-norm + tied embeddings
    ("qwen2-vl-2b", 1),              # M-RoPE + qkv bias + embed input
    ("granite-moe-1b-a400m", 1),     # MoE top-k
    ("mamba2-2.7b", 1),              # SSM decode
    ("jamba-1.5-large-398b", 8),     # hybrid block: attn+mamba+MoE+MLP
]


def test_megakernel_matches_oracle_smoke():
    """Fast-lane megakernel numerics: one dense layer end to end (the
    full per-family sweep below is slow-marked)."""
    _check_megakernel_matches_oracle("deepseek-7b", 1)


@pytest.mark.slow
@pytest.mark.parametrize("arch,layers", FAMILIES)
def test_megakernel_matches_oracle(arch, layers):
    _check_megakernel_matches_oracle(arch, layers)


def _check_megakernel_matches_oracle(arch, layers):
    cfg = dataclasses.replace(get_config(arch).reduced(), n_layers=layers)
    params = jax.tree.map(np.asarray, init_params(cfg, KEY,
                                                  dtype=jnp.float32))
    b, s = 2, 16
    cache = jax.tree.map(np.asarray,
                         init_cache(cfg, b, s, dtype=jnp.float32))
    if cfg.embed_input:
        inp = np.asarray(jax.random.normal(KEY, (b, cfg.d_model))) * 0.1
    else:
        inp = np.array([3, 7])
    seq_lens = np.array([1, 4], np.int32)

    prog = compile_decode_megakernel(cfg, b, s)
    binds = decode_bindings(cfg, params, cache, inp, seq_lens)
    out = MegakernelExecutor(prog, cfg).run_once(binds)
    ref = execute_reference(prog.compiled.graph, binds)
    for k in ref:
        np.testing.assert_allclose(ref[k], out[k], rtol=2e-4, atol=2e-4)

    # and against the JAX model oracle
    jlg, _ = serve_step(jax.tree.map(jnp.asarray, params), cfg,
                        jax.tree.map(jnp.asarray, cache),
                        jnp.asarray(inp), jnp.asarray(seq_lens))
    np.testing.assert_allclose(out["logits"], np.asarray(jlg),
                               rtol=3e-4, atol=3e-4)


def test_single_launch_property():
    """The whole step is ONE kernel: grid length == number of tasks, and
    every task descriptor is consumed in linearized order."""
    cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                              n_layers=2)
    prog = compile_decode_megakernel(cfg, 2, 16)
    assert prog.descs.shape[0] == len(prog.compiled.order)
    # descriptor table is the fixed-size uniform representation (paper §4;
    # words 24-31 carry the software-pipelining prefetch plan, words
    # 32-35 the event-counter wait/signal of the multi-worker runtime)
    assert prog.descs.shape[1] == DESC_WORDS == 36
    # in-place state aliasing: cache2 shares the cache's heap slot
    lay = prog.layout
    assert lay["L0.k_cache2"].offset == lay["L0.k_cache"].offset


def test_descriptor_prefetch_stats():
    cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                              n_layers=2)
    prog = compile_decode_megakernel(cfg, 2, 16)
    # every non-dummy task maps to a known kind (14/15 are the COMM
    # kinds of the multi-chip subsystem)
    assert set(np.unique(prog.descs[:, 0])) <= set(range(16))


def test_kernel_mode_follows_the_heap_device():
    """The CPU interprets the kernel, a TPU runs it compiled, and no other
    platform runs it at all — there is no fallback."""
    from repro.kernels.megakernel.ops import interprets_on

    assert interprets_on("cpu") is True
    assert interprets_on("tpu") is False
    with pytest.raises(RuntimeError, match="gpu"):
        interprets_on("gpu")
    cfg = get_config("deepseek-7b").reduced()
    ex = MegakernelExecutor(compile_decode_megakernel(cfg, 2, 16), cfg)
    assert ex.device.platform == "cpu" and ex.interpret
