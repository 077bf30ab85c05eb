"""The compiled megakernel step, built for a TPU v5e that is described,
not attached.

Each test lowers and compiles the jitted decode step of
``MegakernelExecutor`` with ``interpret=False`` against one chip of a
described ``v5e:2x2`` topology, at published widths cut to one layer,
8 slots × 1024 positions: deepseek-7b as ``chip_smoke.py`` serves it
and mamba2-2.7b, each also with the trace ring on; and the prefill's state
gather and scatter at the cells' sizes.  Shapes only — the 7 GB heap is
a ``ShapeDtypeStruct``.
What Mosaic or XLA:TPU refuses (an unaligned DMA, too much VMEM, a heap
that does not fit the chip) fails here, at no chip time.  A compile that
passes says nothing about results or speed.
"""
from __future__ import annotations

import dataclasses
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

#: v5e HBM: 16 GB per chip
CHIP_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_step(one_chip, arch, *, trace=False):
    from repro.configs import get_config
    from repro.kernels.megakernel.desc import heap_words
    from repro.kernels.megakernel.ops import (MegakernelExecutor,
                                              compile_decode_megakernel)

    cfg = dataclasses.replace(get_config(arch), n_layers=1)
    plan = compile_decode_megakernel(cfg, 8, 1024, trace=trace)
    ex = MegakernelExecutor(plan, cfg)
    heap = jax.ShapeDtypeStruct((1, heap_words(plan.heap_size)),
                                jnp.float32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((ex.step_input_words,), jnp.float32,
                                sharding=one_chip)
    step = jax.jit(ex.step_fn(interpret=False), donate_argnums=(0,))
    return plan, step.lower(heap, vals).compile()


@pytest.mark.parametrize("arch,trace", [("deepseek-7b", False),
                                        ("deepseek-7b", True),
                                        ("mamba2-2.7b", False),
                                        ("mamba2-2.7b", True)],
                         ids=["deepseek-static", "deepseek-trace",
                              "mamba2-static", "mamba2-trace"])
def test_step_compiles_for_v5e(one_chip, no_persistent_cache, arch, trace):
    plan, compiled = _compile_step(one_chip, arch, trace=trace)
    assert plan.heap_size > 0.8e9            # published widths
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # the heap is donated and aliased in place: no second copy
    assert mem.alias_size_in_bytes >= 4 * plan.heap_size
    assert total < CHIP_BYTES, total


@pytest.mark.parametrize("arch,layers", [("deepseek-7b", 1),
                                         ("mamba2-2.7b", 4)],
                         ids=["deepseek-L1", "mamba2-L4"])
def test_state_gather_scatter_compile_in_bounded_memory(
        one_chip, no_persistent_cache, arch, layers):
    """The prefill's state gather and scatter at the cells' sizes (8 slots
    × 1024): the heap is donated to the scatter, and neither makes a
    temporary the size of a padded state span (an SSM state span is
    ~860 MB padded for 21 MB live)."""
    from repro.configs import get_config
    from repro.kernels.megakernel.desc import heap_words
    from repro.kernels.megakernel.ops import (MegakernelExecutor,
                                              compile_decode_megakernel)

    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    plan = compile_decode_megakernel(cfg, 8, 1024)
    ex = MegakernelExecutor(plan, cfg)
    heap = jax.ShapeDtypeStruct((1, heap_words(plan.heap_size)),
                                jnp.float32, sharding=one_chip)
    state = {name: jax.ShapeDtypeStruct(plan.layout[name].shape,
                                        jnp.float32, sharding=one_chip)
             for name, *_ in ex._state_spans}
    span = max(4 * rows * ld for _, _, rows, ld, _ in ex._state_spans)
    gather = ex._jget.lower(heap).compile().memory_analysis()
    scatter = ex._jset.lower(heap, state).compile().memory_analysis()
    assert scatter.alias_size_in_bytes >= 4 * plan.heap_size
    for mem in (gather, scatter):
        assert mem.temp_size_in_bytes < span, (mem.temp_size_in_bytes, span)


def test_compiled_kernel_refuses_unported_plans():
    """Plans the compiled kernel cannot run fail at build time, not on
    the chip: task kinds without a compiled implementation, or a layout
    with unaligned tile DMAs."""
    from repro.configs import get_config
    from repro.kernels.megakernel.kernel import make_megakernel
    from repro.kernels.megakernel.ops import (MegakernelExecutor,
                                              compile_decode_megakernel)

    cfg = get_config("deepseek-7b-reduced")
    moe = compile_decode_megakernel(get_config("granite-moe-1b-a400m-reduced"),
                                    2, 16)
    with pytest.raises(NotImplementedError, match="task kinds"):
        make_megakernel(moe.statics, moe.num_steps, moe.heap_size,
                        interpret=False, kinds=set(moe.descs[:, 0]))
    plan = compile_decode_megakernel(cfg, 2, 16)       # head_dim 32
    assert plan.compiled_layout_errors()
    with pytest.raises(ValueError, match="compiled TPU kernel"):
        MegakernelExecutor(plan, cfg).step_fn(interpret=False)


#: the task kinds of each assigned architecture's reduced plan that the
#: compiled kernel does not implement yet (the MoE kinds: top-k router,
#: gather GEMM, combine); the other seven run compiled
_UNCOMPILED = {
    "deepseek-7b": [], "gemma-7b": [], "mamba2-2.7b": [],
    "mistral-nemo-12b": [], "musicgen-large": [], "qwen1.5-110b": [],
    "qwen2-vl-2b": [],
    "granite-moe-1b-a400m": [9, 10, 11],
    "jamba-1.5-large-398b": [9, 10, 11],
    "llama4-maverick-400b-a17b": [9, 10, 11],
}


def test_uncompiled_table_covers_archs():
    from repro.configs import ARCHS
    assert set(_UNCOMPILED) == set(ARCHS)


@pytest.mark.parametrize("arch", sorted(_UNCOMPILED))
def test_compiled_gate_per_arch(arch):
    """Every assigned architecture's reduced plan meets the compiled
    kernel's build-time gate as the gate says: ``make_megakernel`` takes
    the plan's kinds or names exactly those outside ``COMPILED_KINDS``,
    and ``step_fn(interpret=False)`` refuses a plan with layout errors.
    Plan only, at 2 slots × 16: no compile, no chip."""
    from repro.configs import get_config
    from repro.kernels.megakernel.kernel import COMPILED_KINDS, make_megakernel
    from repro.kernels.megakernel.ops import (MegakernelExecutor,
                                              compile_decode_megakernel)

    cfg = get_config(arch).reduced()
    plan = compile_decode_megakernel(cfg, 2, 16)
    kinds = {int(k) for k in plan.descs[:, 0]}
    missing = sorted(kinds - COMPILED_KINDS)
    assert missing == _UNCOMPILED[arch], missing
    build = lambda: make_megakernel(plan.statics, plan.num_steps,  # noqa: E731
                                    plan.heap_size, interpret=False,
                                    kinds=kinds)
    if missing:
        with pytest.raises(NotImplementedError) as err:
            build()
        assert str(missing) in str(err.value)
    else:
        build()
    if plan.compiled_layout_errors():
        with pytest.raises(ValueError, match="compiled TPU kernel"):
            MegakernelExecutor(plan, cfg).step_fn(interpret=False)
