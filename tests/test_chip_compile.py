"""The compiled megakernel step, built for a TPU v5e that is described,
not attached.

Each test lowers and compiles the jitted decode step of
``MegakernelExecutor`` with ``interpret=False`` against one chip of a
described ``v5e:2x2`` topology, at published widths cut to one layer,
8 slots × 1024 positions: deepseek-7b as ``chip_smoke.py`` serves it
(also with the trace ring on) and mamba2-2.7b.  Shapes only — the 7 GB
heap is a ``ShapeDtypeStruct``.
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
                                        ("mamba2-2.7b", False)],
                         ids=["deepseek-static", "deepseek-trace",
                              "mamba2-static"])
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


def test_compiled_kernel_refuses_unported_plans():
    """Plans the compiled kernel cannot run fail at build time, not on
    the chip: the dynamic scheduler and task kinds without a compiled
    implementation, or a layout with unaligned tile DMAs."""
    from repro.configs import get_config
    from repro.kernels.megakernel.kernel import make_megakernel
    from repro.kernels.megakernel.ops import (MegakernelExecutor,
                                              compile_decode_megakernel)

    cfg = get_config("deepseek-7b-reduced")
    dyn = compile_decode_megakernel(cfg, 2, 16, num_workers=2,
                                    scheduler="dynamic")
    with pytest.raises(NotImplementedError, match="static scheduler"):
        make_megakernel(dyn.statics, dyn.num_steps, dyn.heap_size,
                        interpret=False)
    moe = compile_decode_megakernel(get_config("granite-moe-1b-a400m-reduced"),
                                    2, 16)
    with pytest.raises(NotImplementedError, match="task kinds"):
        make_megakernel(moe.statics, moe.num_steps, moe.heap_size,
                        interpret=False, kinds=set(moe.descs[:, 0]))
    plan = compile_decode_megakernel(cfg, 2, 16)       # head_dim 32
    assert plan.compiled_layout_errors()
    with pytest.raises(ValueError, match="compiled TPU kernel"):
        MegakernelExecutor(plan, cfg).step_fn(interpret=False)
