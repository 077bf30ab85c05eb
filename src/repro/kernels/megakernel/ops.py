"""Megakernel execution: a static plan compiled once, then a persistent
executor that runs every decode step as ONE pallas_call against a
device-resident heap (the paper's compile-once / step-many contract).

``compile_decode_megakernel`` lowers a config's decode step to a
:class:`~.desc.MegakernelPlan`; :class:`MegakernelExecutor` turns the plan
into a live program:

* ``make_megakernel`` + ``jax.jit`` trace happen exactly ONCE per
  executor (assert via ``kernel.make_count()`` / ``trace_count``),
* weights are packed into the f32 heap exactly once at ``upload()``
  (``upload_count``),
* KV-cache / conv / SSM state stays in place across steps — the kernel's
  in-place aliasing plus jit buffer donation keep the heap resident,
* per-step inputs (tokens, seq_lens, live_lens, positions) go through a
  small scatter into the heap (``at[idx].set``) instead of a host-side
  full-heap rebuild,
* the state gather and scatter a prefill round-trips through
  (``read_state`` / ``write_state``) copy the state spans on the device,
  in bounded blocks: no state byte reaches the host.

``tp > 1`` compiles the TP-sharded graph and stamps the plan into a
multi-chip task table (``desc.stamp_multichip``): per-chip descriptor
streams with first-class COMM tasks executing the chunked ring-allreduce
of ``distributed/comm_tasks.py`` over the fused per-chip heap regions.
The executor replicates inputs into every chip region; logits are read
from chip 0 (all chips hold bit-identical outputs, asserted by the
tests).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ...core.compile import CompileOptions, megakernelize
from ...core.decompose import DecomposeConfig, widest_tile
from ...core.lowering import build_decode_graph
from .desc import (STATS_WORDS, TRACE_HEADER, TRACE_WORDS, MegakernelPlan,
                   _align, heap_words, lower_tgraph, stamp_multichip)
from .kernel import make_megakernel

__all__ = ["compile_decode_megakernel", "MegakernelExecutor",
           "STATS_FIELDS", "decode_stats_row", "read_stats_block",
           "interprets_on"]

#: named field map of the per-worker STATS block: counter name → word
#: index.  Words 4 and 13 (``SPILL_WORDS``) are the 2^20-unit spills of
#: ``row_copies`` and ``state_row_copies`` — folded back into those
#: fields by ``decode_stats_row`` instead of surfacing as counters;
#: words 8-11 are reserved.
STATS_FIELDS = {
    "bulk_copies": 0,
    "row_copies": 1,
    "prefetch_tiles": 2,
    "primary_fallbacks": 3,
    "event_waits": 5,
    "event_wait_violations": 6,
    "event_signals": 7,
    "state_row_copies": 12,
}

SPILL_WORDS = {"row_copies": 4, "state_row_copies": 13}
ROW_SPILL_UNIT = 1 << 20

#: most padded heap words one block of a state gather or scatter copies:
#: the copies run block by block, so their temporaries stay this size
#: whatever the state's (an SSM state span is ~860 MB padded)
STATE_BLOCK_WORDS = 1 << 22


def _block_rows(rows: int, ld: int) -> int:
    """The most rows, dividing ``rows``, whose heap span at row stride
    ``ld`` fits ``STATE_BLOCK_WORDS`` (at least one row)."""
    cap = max(1, min(rows, STATE_BLOCK_WORDS // ld))
    return next(d for d in range(cap, 0, -1) if rows % d == 0)


def _gather_span(heap, off: int, rows: int, ld: int, cols: int):
    """The ``(rows, cols)`` tensor whose rows start every ``ld`` words
    from word ``off`` of the ``(1, N)`` heap, cropped block by block on
    the device (traceable)."""
    br = _block_rows(rows, ld)

    def block(i, out):
        img = jax.lax.dynamic_slice(heap, (0, off + i * br * ld),
                                    (1, br * ld))
        return jax.lax.dynamic_update_slice(
            out, img.reshape(br, ld)[:, :cols], (i * br, 0))

    return jax.lax.fori_loop(0, rows // br, block,
                             jnp.zeros((rows, cols), heap.dtype))


def _scatter_span(heap, v, offs, ld: int):
    """Write the ``(rows, cols)`` tensor ``v`` into the ``(1, N)`` heap
    as rows ``ld`` words apart from each word offset in ``offs``, pad
    columns zeroed, block by block (traceable).  Each block is one
    contiguous ``dynamic_update_slice``."""
    rows, cols = v.shape
    br = _block_rows(rows, ld)

    def block(i, heap):
        rows_i = jax.lax.dynamic_slice_in_dim(v, i * br, br)
        img = jnp.pad(rows_i, ((0, 0), (0, ld - cols))).reshape(1, -1)
        for off in offs:
            heap = jax.lax.dynamic_update_slice(heap, img,
                                                (0, off + i * br * ld))
        return heap

    return jax.lax.fori_loop(0, rows // br, block, heap)


def decode_stats_row(v) -> Dict[str, int]:
    """Decode one worker's STATS block (``STATS_WORDS`` f32 words) into
    named integer counters, folding the 2^20-unit spill words back in so
    row counts far past 2^24 rows/launch stay exact."""
    out = {name: int(v[i]) for name, i in STATS_FIELDS.items()}
    for name, i in SPILL_WORDS.items():
        out[name] += ROW_SPILL_UNIT * int(v[i])
    return out


def read_stats_block(heap, stats_offset: int,
                     num_workers: int) -> List[Dict[str, int]]:
    """Read + decode the per-worker STATS blocks from a heap (flat or
    ``(1, N)``, host array or device buffer): one named-counter dict per
    worker lane."""
    flat = np.asarray(
        heap[..., stats_offset : stats_offset + num_workers * STATS_WORDS]
    ).reshape(-1)
    return [decode_stats_row(flat[w * STATS_WORDS : (w + 1) * STATS_WORDS])
            for w in range(num_workers)]


def interprets_on(platform: str) -> bool:
    """Whether the megakernel runs in the Pallas interpreter on a device
    of ``platform``: the CPU interprets, a TPU runs the compiled kernel,
    and nothing else runs it at all."""
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"the megakernel runs on a TPU (compiled) or the "
                       f"CPU (interpreted), not on {platform!r}")


def compile_decode_megakernel(cfg, batch: int, max_seq: int,
                              *, max_rows: int = 8,
                              latency_aware: bool = True,
                              event_fusion: bool = True,
                              pipeline_depth: int = 2,
                              num_workers: int = 1,
                              tp: int = 1,
                              trace: bool = False
                              ) -> MegakernelPlan:
    """Lower cfg's decode step end-to-end: op graph → tGraph → descriptors.

    ``max_rows`` caps tile rows (the megakernel's TM) — decode batches are
    small, so row tiles stay register-friendly.  The kernel's tile width
    TN is the widest tile of the default partition; every matmul is then
    cut into TM × ≤TN tiles (``DecomposeConfig.matmul_cols``), the same
    for any ``num_workers`` or ``tp``.  ``pipeline_depth`` is
    the separation the scheduler enforces between producer→consumer pairs
    (2 = the kernel's double buffer).  ``num_workers`` partitions the
    schedule into W decentralized per-worker descriptor streams
    synchronized through in-heap event counters (paper §5).
    ``tp > 1`` inserts AllReduce ops into the graph (paper §6.5) and
    stamps the lowered plan into per-chip task tables whose collectives
    run as in-kernel COMM tasks.
    ``trace=True`` appends the per-task trace ring to the heap and makes
    the kernel timestamp every executed slot (``obs`` decodes it); off,
    the layout and outputs are bitwise identical to the untraced build.
    """
    g = build_decode_graph(cfg, batch, max_seq, tp=tp)
    dec = DecomposeConfig(max_rows=max_rows)
    tn = _align(widest_tile(g, dec))
    opts = CompileOptions(
        decompose=dataclasses.replace(dec, matmul_cols=tn),
        latency_aware_schedule=latency_aware,
        event_fusion=event_fusion,
        pipeline_depth=pipeline_depth,
        num_workers=num_workers,
        trace=trace,
    )
    compiled = megakernelize(g, opts)
    plan = lower_tgraph(compiled, cfg, tn=tn, trace=trace)
    if tp > 1:
        plan = stamp_multichip(plan, tp)
    return plan


class MegakernelExecutor:
    """The live half of a compiled megakernel program.

    Lifecycle::

        ex = MegakernelExecutor(plan, cfg)     # ONE make_megakernel
        ex.upload(plan.build_heap(bindings))   # ONE full heap upload
        logits = ex.step(tokens, seq_lens)     # partial update + 1 launch
        logits = ex.step(tokens, seq_lens + 1) # state carried in-heap

    The heap lives on the default device as one ``(1, N)`` f32 row; the
    device's platform decides once whether the kernel is interpreted
    (CPU) or compiled (TPU) — see :func:`interprets_on`.
    """

    def __init__(self, plan: MegakernelPlan, cfg):
        self.plan = plan
        self.cfg = cfg
        self.device = jax.devices()[0]
        self.interpret = interprets_on(self.device.platform)
        self.trace_count = 0       # jit traces of the step function
        self.upload_count = 0      # full heap uploads (weights included)
        self.step_count = 0
        g = plan.compiled.graph
        classes = plan.input_classes()
        self._per_step: List[str] = classes["per_step"]
        self._state_inputs: List[str] = classes["state"]
        # multi-chip plans mirror every tensor slot into C per-chip heap
        # regions; per-step scatters and state resets replicate across
        # the mirrors, reads (logits, read_state) come from chip 0
        self._n_chips = max(1, plan.n_chips)
        self._chip_offsets = (np.arange(self._n_chips, dtype=np.int64)
                              * plan.chip_stride)

        # ---- heap spans rewritten before every launch ----
        # Every update of the resident heap is a contiguous span written
        # with ``dynamic_update_slice``: an index scatter into the (1, N)
        # heap makes XLA:TPU relayout the whole heap into a temporary.
        # Per-step inputs are written as whole row slots (pad columns 0).
        self._entries = [(name, plan.layout[name])
                         for name in self._per_step]
        spans = [(sl.offset + c, sl.rows * sl.ld)
                 for c in self._chip_offsets for _, sl in self._entries]
        # the in-heap event-counter table is re-zeroed with the inputs
        # (the kernel increments counters during the launch, so every
        # launch starts from a clean table)
        self._n_events = plan.num_events
        if self._n_events:
            spans.append((plan.event_offset, self._n_events))
        # trace ring: only the logical tick counter at the ring head
        # needs re-zeroing — the kernel rewrites every record slot each
        # launch (idle/noop slots included)
        if plan.trace:
            spans.append((plan.ring_offset, 1))
        self._step_spans = spans
        self._descs = jnp.asarray(plan.descs)

        # ---- state tensors: whole spans (read/write), per-slot spans
        # (reset) ----
        self._batch = g.spec("seq_lens").shape[0]
        self._state_spans = []
        for name in self._state_inputs:
            slot = plan.layout[name]
            cols = slot.shape[-1] if slot.shape else 1
            self._state_spans.append((name, slot.offset, slot.rows,
                                      slot.ld, cols))
        slot_spans = []
        for name in self._state_inputs:
            slot = plan.layout[name]
            rpb = slot.rows // slot.shape[0]   # heap rows per batch entry
            slot_spans += [(slot.offset + c, rpb * slot.ld)
                           for c in self._chip_offsets]
        self.state_scatter_count = 0

        # ---- the ONE kernel + the ONE jitted step ----
        self._jrun = jax.jit(self.step_fn(self.interpret),
                             donate_argnums=(0,))

        def zero_slot(heap, b):
            for off, n in slot_spans:
                heap = jax.lax.dynamic_update_slice(
                    heap, jnp.zeros((1, n), heap.dtype), (0, off + b * n))
            return heap

        # state gather (chip 0) and scatter (every chip) stay on the
        # device: one span after another, each in bounded blocks
        def get_state(heap):
            return {name: _gather_span(heap, off, rows, ld, cols).reshape(
                        plan.layout[name].shape)
                    for name, off, rows, ld, cols in self._state_spans}

        def set_state(heap, tensors):
            for name, off, rows, ld, cols in self._state_spans:
                heap = _scatter_span(
                    heap, tensors[name].reshape(rows, cols),
                    [int(off + c) for c in self._chip_offsets], ld)
            return heap

        self._jzero = jax.jit(zero_slot, donate_argnums=(0,))
        self._jget = jax.jit(get_state)
        self._jset = jax.jit(set_state, donate_argnums=(0,))
        self._heap: Optional[jax.Array] = None

    def step_fn(self, interpret: bool):
        """The decode step before ``jit``: write the per-step inputs into
        the ``(1, heap_words(heap_size))`` heap, launch the kernel once,
        slice out the logits and the per-worker counter blocks.
        ``(heap, vals) -> (heap, logits, stats)``.
        ``interpret`` picks the Pallas interpreter or the compiled
        kernel; the executor passes its device's choice."""
        plan = self.plan
        if not interpret:
            errs = plan.compiled_layout_errors()
            if errs:
                raise ValueError("plan cannot run on the compiled TPU "
                                 "kernel: " + "; ".join(errs))
        kern = make_megakernel(plan.statics, plan.num_steps, plan.heap_size,
                               interpret=interpret,
                               kinds=np.unique(plan.descs[:, 0]))
        lg = plan.layout["logits"]
        lg_cols = lg.shape[-1]
        stats = slice(plan.stats_offset,
                      plan.stats_offset + plan.num_workers * STATS_WORDS)

        def _step(heap, vals):
            self.trace_count += 1  # python side effect: runs at trace only
            pos = 0
            for off, n in self._step_spans:
                heap = jax.lax.dynamic_update_slice(
                    heap, vals[None, pos : pos + n], (0, off))
                pos += n
            heap = kern(self._descs, heap)
            logits = heap[:, lg.offset : lg.offset + lg.rows * lg.ld]
            logits = logits.reshape(lg.rows, lg.ld)[:, :lg_cols]
            return heap, logits, heap[0, stats]

        return _step

    def _jstep(self, heap, vals):
        """The jitted decode step without its counter blocks:
        ``(heap, vals) -> (heap, logits)`` (``bench/tools/backend_compare.py``
        times it)."""
        heap, logits, _ = self._jrun(heap, vals)
        return heap, logits

    @property
    def step_input_words(self) -> int:
        """Length of the packed per-step input vector ``step_fn`` takes."""
        return sum(n for _, n in self._step_spans)

    def lowered_step(self):
        """The jitted decode step lowered (not compiled) for this plan's
        heap and input shapes."""
        return self._jrun.lower(
            jax.ShapeDtypeStruct((1, heap_words(self.plan.heap_size)),
                                 jnp.float32),
            jax.ShapeDtypeStruct((self.step_input_words,), jnp.float32))

    # ------------------------------------------------------------ helpers
    def _pack_step_inputs(self, tokens_or_embeds, seq_lens,
                          positions=None) -> jax.Array:
        lens = np.asarray(seq_lens, np.int32)
        vals: Dict[str, np.ndarray] = {
            "seq_lens": lens, "live_lens": lens + 1}
        if self.cfg.embed_input:
            vals["h0"] = np.asarray(tokens_or_embeds, np.float32)
        else:
            vals["tokens"] = np.asarray(tokens_or_embeds, np.int32)
        if "positions" in self._per_step:
            pos = np.asarray(lens if positions is None else positions)
            if self.cfg.mrope_sections is not None and pos.ndim == 1:
                pos = np.stack([pos] * 3, axis=-1)
            vals["positions"] = pos
        flat = []
        for name, sl in self._entries:
            img = np.zeros((sl.rows, sl.ld), np.float32)
            cols = sl.shape[-1] if sl.shape else 1
            img[:, :cols] = np.asarray(vals[name],
                                       np.float32).reshape(sl.rows, cols)
            flat.append(img.ravel())
        flat = flat * self._n_chips
        if self._n_events:
            flat.append(np.zeros((self._n_events,), np.float32))
        if self.plan.trace:
            flat.append(np.zeros((1,), np.float32))   # tick counter
        return jnp.asarray(np.concatenate(flat))

    # ------------------------------------------------------------- public
    def upload(self, heap: np.ndarray) -> None:
        """Full heap upload — happens once per ``bind`` (weights + state).
        ``heap`` is the flat ``heap_words(heap_size)``-word host image
        (``plan.build_heap``)."""
        heap = np.asarray(heap, np.float32)
        assert heap.shape == (heap_words(self.plan.heap_size),), heap.shape
        self._heap = jax.device_put(heap.reshape(1, -1), self.device)
        self.upload_count += 1

    def reset_state(self, slot: Optional[int] = None) -> None:
        """Zero cache/conv/SSM state in place on device (one batch row, or
        all of them) — a partial update, not a re-upload."""
        assert self._heap is not None, "upload() before reset_state()"
        if not self._state_spans:
            return
        for b in range(self._batch) if slot is None else (slot,):
            self._heap = self._jzero(self._heap, jnp.int32(b))

    def step(self, tokens_or_embeds, seq_lens, positions=None) -> np.ndarray:
        """One decode step inside the persistent kernel; returns logits
        (B, vocab).  State advances in the device-resident heap.

        Spans (``obs.span``): ``step``, and inside it ``step.pack``
        (input image, host to device), ``step.launch`` (the jitted call
        until it returns), ``step.wait`` (until the logits are ready) and
        ``step.readback`` (logits to the host).  While they record, the
        ``step`` span also carries the launch's ``row_copies`` and
        ``state_row_copies`` counters, an output of the same jitted call:
        on its way to the host while the logits are awaited, and read
        after the readback."""
        assert self._heap is not None, "upload() before step()"
        with obs.span("step") as sp:
            with obs.span("step.pack"):
                vals = self._pack_step_inputs(tokens_or_embeds, seq_lens,
                                              positions)
            with obs.span("step.launch"):
                self._heap, logits, stats = self._jrun(self._heap, vals)
            if sp.recording:
                stats.copy_to_host_async()
            with obs.span("step.wait"):
                logits.block_until_ready()
            with obs.span("step.readback"):
                out = np.asarray(logits)
            self.step_count += 1
            if sp.recording:
                per_worker = read_stats_block(np.asarray(stats), 0,
                                              self.plan.num_workers)
                sp.set(row_copies=sum(d["row_copies"] for d in per_worker),
                       state_row_copies=sum(d["state_row_copies"]
                                            for d in per_worker))
            # the step's device buffers are freed inside its span
            del vals, logits, stats
        return out

    def worker_counters(self) -> List[Dict[str, int]]:
        """Per-worker kernel counters for the LAST step, one dict per
        worker lane, read from the reserved per-worker blocks at the heap
        tail (each worker re-zeroes its block at grid step 0 of every
        launch): bulk tile DMAs issued, row copies inside them and those
        of them that read or write state (with the 2^20-unit spill words
        folded back in), prefetch tiles issued, primary tiles
        demand-loaded (pipeline misses), event waits checked, event-wait
        violations (a compiler bug if nonzero) and event signals."""
        assert self._heap is not None, "upload() before worker_counters()"
        return read_stats_block(self._heap, self.plan.stats_offset,
                                self.plan.num_workers)

    def pipeline_counters(self) -> Dict[str, int]:
        """Kernel counters for the LAST step summed over the worker
        lanes (see :meth:`worker_counters` for the per-worker blocks):
        bulk tile DMAs issued, row copies inside them (what the
        pre-pipelining kernel issued as individual DMAs), prefetch tiles
        issued, primary tiles demand-loaded (pipeline misses), plus the
        event-counter traffic of the W-worker runtime."""
        per_worker = self.worker_counters()
        return {k: sum(d[k] for d in per_worker) for k in STATS_FIELDS}

    def task_ring(self) -> np.ndarray:
        """Raw trace-ring records for the LAST step: an
        ``(num_steps * num_workers, TRACE_WORDS)`` f32 array in grid-slot
        order (see ``desc.TRACE_WORDS`` for the record schema).  The
        ``obs`` package decodes this into a typed ``TaskTrace``."""
        assert self.plan.trace, "plan compiled without trace=True"
        assert self._heap is not None, "upload() before task_ring()"
        off = self.plan.ring_offset + TRACE_HEADER
        n = self.plan.num_steps * self.plan.num_workers
        flat = np.asarray(self._heap[:, off : off + n * TRACE_WORDS])[0]
        return flat.reshape(n, TRACE_WORDS)

    def read_heap(self) -> np.ndarray:
        """Flat host copy of the resident heap (state inspection /
        snapshots)."""
        assert self._heap is not None, "upload() before read_heap()"
        return np.array(self._heap)[0]  # writable host copy

    def write_heap(self, heap: np.ndarray) -> None:
        """Replace the resident heap (state restore); counts as an upload."""
        self.upload(heap)

    def read_state(self) -> Dict[str, jax.Array]:
        """Every state tensor of the resident heap (chip 0), cropped on
        the device — its span only (O(state), weights never move, nothing
        reaches the host).  Returns fresh graph-shaped device arrays
        keyed by state input name: later steps leave them as they are."""
        assert self._heap is not None, "upload() before read_state()"
        return self._jget(self._heap)

    def write_state(self, tensors: Dict[str, Any]) -> None:
        """Write new values for every state tensor into the resident
        heap on the device (partial update — weights are never re-moved,
        pad columns stay 0, the write is replicated into every chip's
        heap region).  ``tensors`` maps state input names to graph-shaped
        arrays, device or numpy."""
        assert self._heap is not None, "upload() before write_state()"
        if not self._state_spans:
            return
        vals = {name: jnp.asarray(tensors[name], jnp.float32)
                for name, *_ in self._state_spans}
        self._heap = self._jset(self._heap, vals)
        self.state_scatter_count += 1

    def run_once(self, bindings: Dict[str, np.ndarray]
                 ) -> Dict[str, np.ndarray]:
        """Build the heap from full bindings, run one step, return every
        graph output (legacy one-shot semantics)."""
        self.upload(self.plan.build_heap(bindings))
        lens = np.asarray(bindings["seq_lens"], np.int32)
        if self.cfg.embed_input:
            tok = bindings["h0"]
        else:
            tok = bindings["tokens"]
        self.step(tok, lens, bindings.get("positions"))
        heap = self.read_heap()
        return {name: self.plan.read_output(heap, name)
                for name in self.plan.compiled.graph.outputs}
