"""The megakernel's state gather and scatter stay on the device.

``PallasProgram.get_state`` crops every state span out of the resident
heap and assembles the ``init_cache`` pytree on the device;
``set_state`` pads the tensors back into their spans there.  No state
byte goes to the host.  Checked on tiny deepseek (K/V) and mamba2 (conv
and SSM state) programs on the CPU, whose heap is a device array like
the chip's.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpk
from repro.api.program import _state_map
from repro.configs import get_config
from repro.kernels.megakernel import ops
from repro.models import init_cache, init_params

B, S = 2, 16


@pytest.fixture(scope="module", params=["deepseek-7b", "mamba2-2.7b"])
def served(request):
    """A megakernel program with live state: one prefill, one step."""
    cfg = dataclasses.replace(get_config(request.param).reduced(),
                              n_layers=1)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prog = mpk.compile(cfg, B, S, backend="megakernel").bind(params)
    prog.init_state()
    rng = np.random.default_rng(0)
    lens = np.array([3, 2], np.int32)
    prog.prefill(rng.integers(1, cfg.vocab, size=(B, 3)).astype(np.int32),
                 np.zeros(B, np.int32), lens)
    prog.step(rng.integers(1, cfg.vocab, size=B).astype(np.int32), lens)
    return cfg, params, prog, lens + 1


def _host_state(prog):
    """The state pytree cropped out of a host copy of the heap."""
    heap = prog.executor.read_heap()
    state = {k: np.zeros(s.shape, np.float32) for k, s in jax.eval_shape(
        lambda: init_cache(prog.cfg, B, S, dtype=jnp.float32)).items()}
    for ent in _state_map(prog.cfg):
        sl = prog.plan.layout[ent["in"]]
        img = heap[sl.offset : sl.offset + sl.rows * sl.ld]
        leaf = state[ent["key"]]
        leaf[ent["blk"], ent["idx"]] = img.reshape(sl.rows, sl.ld)[
            :, :sl.shape[-1]].reshape(leaf.shape[2:])
    return state


def test_state_round_trip_leaves_heap_bitwise(served):
    _, _, prog, _ = served
    before = prog.executor.read_heap()
    scatters = prog.executor.state_scatter_count
    prog.set_state(prog.get_state())
    assert np.array_equal(prog.executor.read_heap(), before)  # pads too
    assert prog.executor.state_scatter_count == scatters + 1


def test_get_state_is_device_arrays_equal_to_host_crop(served):
    _, _, prog, _ = served
    got, want = prog.get_state(), _host_state(prog)
    assert set(got) == set(want)
    for k in want:
        assert isinstance(got[k], jax.Array), k
        assert got[k].dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got[k]), want[k], k)
    assert any(np.abs(v).sum() > 0 for v in want.values())  # live state


def test_state_gather_and_scatter_make_no_transfer(served):
    """Under the transfer guards.  On the CPU a device array's host view
    is zero-copy and passes the device-to-host guard, so there the
    host-to-device guard is what sees a round trip through the host (its
    upload half); on a TPU both do."""
    _, _, prog, _ = served
    before = prog.executor.read_heap()
    with jax.transfer_guard_device_to_host("disallow"), \
            jax.transfer_guard_host_to_device("disallow_explicit"):
        state = prog.get_state()
        prog.set_state(state)
        raw = prog.executor.read_state()
        prog.executor.write_state(raw)
    for leaf in [*state.values(), *raw.values()]:
        assert isinstance(leaf, jax.Array)
        assert not isinstance(leaf, np.ndarray)
    assert np.array_equal(prog.executor.read_heap(), before)


def test_snapshot_survives_steps_and_two_reference_sets(served):
    """The ``decode_parity`` pattern: a snapshot taken before the program
    steps on, set twice into the ``jax`` reference, whose step donates
    its cache."""
    cfg, params, prog, lens = served
    snap = prog.get_state()
    toks = np.random.default_rng(1).integers(
        1, cfg.vocab, size=(2, B)).astype(np.int32)
    got = [prog.step(t, lens + i) for i, t in enumerate(toks)]
    ref = mpk.compile(cfg, B, S, backend="jax").bind(params)
    runs = []
    for _ in range(2):
        ref.set_state(snap)
        runs.append([ref.step(t, lens + i) for i, t in enumerate(toks)])
    for a, b, g in zip(*runs, got):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(g, a, rtol=3e-4, atol=3e-4)
    # and the megakernel takes the snapshot back: its state before the steps
    prog.set_state(snap)
    np.testing.assert_allclose(prog.step(toks[0], lens), got[0],
                               rtol=0, atol=0)


@pytest.mark.parametrize("rows,ld,cols,block_words", [
    (12, 256, 100, 1024),    # 4-row blocks
    (13, 128, 128, 512),     # 13 is prime: 1-row blocks
    (8, 384, 256, 1 << 22),  # one block
])
def test_span_copies_in_blocks(monkeypatch, rows, ld, cols, block_words):
    """Gather and scatter of one span, cut into blocks: the same words as
    a host crop and pad, at every mirror offset, nothing else touched."""
    monkeypatch.setattr(ops, "STATE_BLOCK_WORDS", block_words)
    rng = np.random.default_rng(rows)
    offs = [40, 40 + rows * ld + 96]            # two chip mirrors
    heap = rng.standard_normal((1, offs[1] + rows * ld + 50)).astype(
        np.float32)
    span = heap[0, offs[0] : offs[0] + rows * ld].reshape(rows, ld)
    got = jax.jit(lambda h: ops._gather_span(h, offs[0], rows, ld, cols))(
        heap)
    np.testing.assert_array_equal(np.asarray(got), span[:, :cols])

    v = rng.standard_normal((rows, cols)).astype(np.float32)
    out = np.asarray(jax.jit(
        lambda h, v: ops._scatter_span(h, v, offs, ld))(heap, v))
    want = heap.copy()
    for off in offs:
        img = np.zeros((rows, ld), np.float32)
        img[:, :cols] = v
        want[0, off : off + rows * ld] = img.ravel()
    np.testing.assert_array_equal(out, want)
