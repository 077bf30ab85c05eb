"""Megakernel lowering: CompiledTGraph → (heap layout, task descriptors).

This is the TPU analogue of MPK's task-description generation (§4.2 /
§5.3): every task becomes a fixed-size int32 descriptor (``DESC_WORDS``
= 36 words ≈ the paper's 352-byte descriptions), prefetched into SMEM
via Pallas scalar prefetch before the grid step executes — the direct
analogue of the paper's task-description prefetching.

Heap: one flat f32 buffer holding every graph tensor.  A tensor of shape
``(..., cols)`` is stored as ``rows = prod(shape[:-1])`` rows with padded
row stride ``ld = align128(cols + TN)`` so that any fixed-width (TN) tile
DMA stays inside its own row slot — tile reads/writes never clobber
neighbours and masking handles the tail columns.

State aliasing: ``cache_update`` / ``conv1d_update`` / ``ssm_update``
outputs alias their input state region (in-place update), exactly like the
persistent kernel on real hardware; the SSA tGraph interpreter remains the
copying oracle.

Descriptor layout (int32 × 36) — field use per kind documented inline:
   0 kind   1 m      2 n      3 k      4 out_off 5 ldo
   6 a_off  7 lda    8 b_off  9 ldb   10 c_off  11 ldc
  12 d_off 13 ldd   14 act   15 aux0  16 aux1   17 fbits0
  18 fbits1 19 e_off 20 lde  21 aux2  22 aux3   23 aux4

Words 24-31 are the compiler-emitted **prefetch plan** (§5 software
pipelining) consumed by the kernel's double-buffered pipeline:

  24 pf_off  25 pf_ld  26 pf_rows   the NEXT task in this worker's
     stream — the kernel issues this as one bulk async DMA into the B
     side of the worker's ping-pong buffer while the current slot
     computes.  ``pf_rows == 0`` means no prefetch (next slot has no
     regular primary tile, or its tile overlaps something any worker
     may write in this or the next step — the hazard analysis below).
  27 self_pf                        1 iff THIS task's primary tile was
     prefetched by its stream predecessor (wait on the slot semaphore
     instead of demand-loading).
  28 sp_off  29 sp_ld  30 sp_rows   this task's own primary record (the
     wait/demand-load reconstruction — the kernel never decodes two
     descriptors per step).  Equal to the stream predecessor's words
     24-26 whenever ``self_pf == 1`` (asserted at lowering).
  31 reserved

Words 32-35 are the **event-counter synchronization** of the W-worker
decentralized runtime (paper §5.1): each cross-worker dependency edge is
covered by an event counter resident in the heap at ``event_offset``
(one f32 word per synchronizing event, zeroed before every launch):

  32 wait_ev   event-table index this task must WAIT on before compute,
     -1 when every producer runs earlier on this task's own worker
     (program order covers the dependency — no event needed).
  33 wait_cnt  the trigger count: the counter's expected value, i.e. the
     number of tasks signaling the event.  Interpret mode executes the
     (step, worker) grid sequentially in an order the compiler proved
     dependency-safe, so the wait degrades to a *checked assertion*:
     counter != wait_cnt is counted as an event-wait violation in the
     stats block (a compiler bug, asserted zero by the tests).
  34 sig_ev    event-table index this task increments after its stores
     land, -1 if no consumer waits on it.
  35 affinity  (dynamic scheduler only) the worker pool newly-ready
     tasks are enqueued onto — the static partition's ``worker_of``
     placement hint; reserved (0) under the static scheduler.

Every prefetch row copy is TN elements wide: row-slot padding
(``ld >= cols + TN``) guarantees a TN-wide read from any legal element
offset stays inside its own row slot, so one static width serves every
task kind.

Multi-worker lowering: the compiler's :class:`~...core.schedule.WorkerPartition`
assigns every task a ``(worker, step)`` coordinate; the descriptor table
becomes a ``(num_steps * W, DESC_WORDS)`` grid (row ``step * W + worker``),
with noop descriptors padding the steps a worker sits out.  Padding slots
still run the prefetch phase, so a worker's double buffer stays warm
across its idle steps.

The heap tail carries the event-counter table (``num_events`` f32 words
at ``event_offset``) followed by a per-worker ``STATS_WORDS``-sized DMA/
event counter block (written by the kernel itself, read back via
``MegakernelExecutor.pipeline_counters()`` / ``worker_counters()``) at
``stats_offset``.

Dynamic scheduler (``scheduler="dynamic"``, see
``runtime/dyn_sched.py`` — the protocol's source of truth): the
descriptor table becomes **schedule-order-free** — one flat row per
linearized task (row id == linearized position, no step padding), each
carrying its event wait/signal words (32-34, emitted for EVERY event,
not just the cross-worker cut) and its affinity worker (35).  The grid
is ``(ceil(T / W), W)`` pop slots; which task a slot runs is decided at
execution time by the in-heap ready queues.  Between the event table
and the stats blocks the heap gains:

  ``queue_offset``   W per-worker ready pools (``QUEUE_CAP`` = 128 f32
                     words each: a descriptor-row id, or ``QUEUE_EMPTY``)
                     followed by the shared overflow queue
                     (``dyn.overflow_cap`` words),
  ``qc_offset``      per-pool [pushed, popped] cursor counters
                     (2 × (W + 1) words, pushed pre-charged with the
                     initial ready image),
  ``trace_offset``   the pop trace: one word per grid slot recording
                     the popped row id (``QUEUE_EMPTY`` for idle slots)
                     — asserted equal to ``dyn_sched.replay_sequential``
                     by the tests.

The executor re-writes the initial queue image, cursor counters and
event zeros through the per-step scatter before every launch; the
consumer lists live in a second scalar-prefetch operand
(``DynSchedPlan.sched_table()``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ...core.compile import CompiledTGraph
from ...core.graph import OpKind

__all__ = ["KIND_CODES", "DESC_WORDS", "STATS_WORDS", "TRACE_WORDS",
           "TRACE_HEADER", "PER_STEP_INPUTS", "LANE", "heap_words",
           "MegakernelPlan", "MegakernelProgram", "lower_tgraph",
           "stamp_multichip"]

#: graph inputs that change every decode step — everything else in the heap
#: (weights, caches, SSM/conv state) is uploaded once and lives on device
PER_STEP_INPUTS = ("tokens", "h0", "positions", "seq_lens", "live_lens")

DESC_WORDS = 36

#: f32 words reserved PER WORKER at the heap tail for the
#: kernel-maintained counters: [0] bulk tile DMAs, [1] row copies inside
#: them, [2] prefetch tiles issued, [3] primary tiles demand-loaded
#: (pipeline misses), [4] 2^20-unit spill of [1], [5] event waits
#: checked, [6] event-wait violations (must stay 0), [7] event signals,
#: and — dynamic scheduler only, zero under static — [8] pops from the
#: worker's own pool, [9] pops from the shared overflow queue,
#: [10] steals from other workers' pools, [11] idle grid slots
STATS_WORDS = 12

#: f32 words PER GRID SLOT in the optional trace ring
#: (``CompileOptions.trace``): [0] worker lane, [1] descriptor row
#: (-1 = dynamic idle slot), [2] kind code, [3] logical start tick,
#: [4] logical end tick, [5] pop source (-1 static / 0 own / 1 overflow
#: / 2 steal), [6] event-wait trigger count, [7] reserved
TRACE_WORDS = 8

#: words at the head of the trace ring, before the records: word 0 is
#: the global logical tick counter the kernel fetch-and-increments; the
#: rest is padding so records start aligned to ``TRACE_WORDS``
TRACE_HEADER = 8

KIND_CODES = {
    "noop": 0,
    OpKind.MATMUL: 1,
    OpKind.RMSNORM: 2,
    OpKind.ROPE: 3,
    OpKind.GLU_MUL: 4,
    OpKind.RESIDUAL_ADD: 5,
    OpKind.ELEMENTWISE: 5,          # scale-add, b absent
    OpKind.ATTENTION_DECODE: 6,
    OpKind.CACHE_UPDATE: 7,
    OpKind.EMBED_LOOKUP: 8,
    OpKind.SOFTMAX_TOPK: 9,
    OpKind.MOE_GATHER_GEMM: 10,
    OpKind.MOE_COMBINE: 11,
    OpKind.SSM_UPDATE: 12,
    OpKind.CONV1D_UPDATE: 13,
    "remote_copy": 14,              # COMM: neighbour send (chunk → peer
                                    #       staging, then event signal)
    OpKind.ALLREDUCE: 15,           # COMM: owner-masked init / arrival
                                    #       accumulate / arrival store
}

#: COMM task codes (the multi-chip subsystem, ``distributed/comm_tasks``).
#: Both kinds move a per-row column window over ``m`` (word 1) rows —
#: chunking the REAL row width keeps the pad columns of ld-aligned
#: tensors out of the chunk partition, so every chip's owned chunk
#: carries live data.  ``REMOTE_COPY`` words: 1 rows, 3 window words per
#: row, 4 dst_off (peer chip's staging, absolute, packed), 5 dst row
#: stride, 6 src_off, 7 src row stride, 10 comm semaphore lane (= peer
#: chip; consumed by the real remote-DMA path, informational under the
#: fused transport), 21 peer chip, 22 chunk id, 23 chunk count; the
#: arrival event it signals rides the standard word 34.
#: ``ALLREDUCE_CHUNK`` words: 1/3/4/5/6/7 as above, 14 arrival mode
#: (0 owner-masked init / 1 accumulate / 2 store), 15 owned-window start
#: (window-relative cols, init only), 16 owned-window length, 21-23 as
#: above; its wait rides the standard words 32-33.
REMOTE_COPY_CODE = 14
AR_CHUNK_CODE = 15

_ACT_IDS = {None: 0, "identity": 0, "silu": 1, "gelu": 2}

#: lane width of one heap tile: on a TPU the heap is a ``(1, N)`` f32 row
#: tiled ``(1, 128)``, so the compiled kernel moves whole 128-word tiles
#: from 128-aligned offsets
LANE = 128


def heap_words(heap_size: int) -> int:
    """Length of the device heap for a plan of ``heap_size`` words: whole
    128-word tiles plus one, so a 256-word block staged from the tile
    holding any word stays inside the array."""
    return -(-heap_size // LANE) * LANE + LANE


def _align(n: int, a: int = 128) -> int:
    return (n + a - 1) // a * a


def _fbits(x: float) -> int:
    return int(np.float32(x).view(np.int32))


@dataclasses.dataclass
class TensorSlot:
    offset: int       # heap element offset of [0, ..., 0]
    ld: int           # row stride (elements) of the last dim
    shape: Tuple[int, ...]

    @property
    def rows(self) -> int:
        r = 1
        for s in self.shape[:-1]:
            r *= s
        return r

    def elem(self, *idx: int) -> int:
        """Heap offset of element ``idx`` (last index is a column)."""
        assert len(idx) == len(self.shape)
        row = 0
        for s, i in zip(self.shape[:-1], idx[:-1]):
            row = row * s + i
        return self.offset + row * self.ld + idx[-1]


@dataclasses.dataclass
class MegakernelPlan:
    """The *static* half of a compiled megakernel: descriptor table, heap
    layout and kernel statics.  Everything here is a pure function of
    (graph, cfg) — no device state.  The live half (resident heap, jitted
    step, incremental input binding) is ``ops.MegakernelExecutor``."""

    compiled: CompiledTGraph
    descs: np.ndarray                 # (num_steps * W, DESC_WORDS) int32
    layout: Dict[str, TensorSlot]
    heap_size: int
    statics: Dict[str, Any]           # compile-time kernel parameters
    #: heap offset of the kernel-maintained per-worker counter blocks
    stats_offset: int = 0
    #: worker count of the lowered (step, worker) grid
    num_workers: int = 1
    #: grid steps (max padded queue length across workers)
    num_steps: int = 0
    #: heap offset of the event-counter table (one f32 word per event)
    event_offset: int = 0
    #: number of in-heap event counters (0 when W == 1: program order
    #: covers every dependency, no cross-worker cut exists).  Under the
    #: dynamic scheduler EVERY event with producers and consumers gets a
    #: counter — the counters ARE the dispatch mechanism.
    num_events: int = 0
    #: "static" (per-worker descriptor streams, PR 4) or "dynamic"
    #: (heap-resident ready queues, ``runtime/dyn_sched.py``)
    scheduler: str = "static"
    #: the dynamic-scheduler plan (None under the static scheduler)
    dyn: Any = None
    #: heap offset of the ready pools (+ overflow queue right after)
    queue_offset: int = 0
    #: heap offset of the per-pool [pushed, popped] cursor counters
    qc_offset: int = 0
    #: heap offset of the pop trace (one word per grid slot)
    trace_offset: int = 0
    #: chips of the stamped multichip plan (1 = single-chip).  At C > 1
    #: the heap is C per-chip tensor regions (each ``chip_stride`` words,
    #: the fused transport of ``distributed/comm_tasks``) followed by the
    #: shared event table, the collectives' staging buffers and the
    #: per-worker stats blocks; the grid is ``C * num_workers`` lanes
    #: wide per chip-stamped step.
    n_chips: int = 1
    #: words per per-chip tensor region (0 when single-chip)
    chip_stride: int = 0
    #: trace ring enabled (``CompileOptions.trace``) — the kernel writes
    #: one ``TRACE_WORDS`` record per grid slot after the stats blocks
    trace: bool = False
    #: heap offset of the trace ring (tick header + records); 0 when off
    ring_offset: int = 0

    # ------------------------------------------------- pipeline contract
    def pipeline_stats(self) -> Dict[str, Any]:
        """The static half of the schedule→kernel pipeline contract:
        scheduler stalls plus the prefetch plan's coverage over the
        descriptor table (the dynamic half — actual bulk-DMA counts — is
        ``MegakernelExecutor.pipeline_counters()``)."""
        s = self.compiled.stats
        kinds = self.descs[:, 0]
        prefetchable = int(np.isin(kinds, list(_PRIMARY_ROWS_M)
                                   + [KIND_CODES[OpKind.EMBED_LOOKUP]]).sum())
        prefetched = int((self.descs[:, 27] == 1).sum())
        return {
            "stalls": s.get("pipeline_stalls", 0),
            "stalls_naive": s.get("pipeline_stalls_naive",
                                  s.get("pipeline_stalls", 0)),
            "pipeline_depth": s.get("pipeline_depth", 2),
            "prefetchable_tasks": prefetchable,
            "prefetched_tasks": prefetched,
            "prefetch_coverage": prefetched / max(1, prefetchable),
        }

    # ---------------------------------------------------- input classes
    def input_classes(self) -> Dict[str, List[str]]:
        """Partition graph inputs into ``per_step`` (tokens/positions/
        lengths — rewritten every step), ``state`` (KV cache, conv and SSM
        state — in-place aliased, stays device-resident) and ``weights``
        (uploaded exactly once at bind)."""
        g = self.compiled.graph
        state = set()
        for op in g.ops:
            amap = _ALIAS_OPS.get(op.kind)
            if amap:
                for in_i in amap.values():
                    state.add(op.inputs[in_i])
        per_step = [n for n in g.inputs if n in PER_STEP_INPUTS]
        weights = [n for n in g.inputs
                   if n not in state and n not in PER_STEP_INPUTS]
        return {"per_step": per_step,
                "state": [n for n in g.inputs if n in state],
                "weights": weights}

    def build_heap(self, bindings: Dict[str, np.ndarray]) -> np.ndarray:
        """Pack bindings into the ``heap_words(heap_size)``-word heap —
        replicated into every chip's region under a multichip plan (the
        TP model's SPMD inputs)."""
        heap = np.zeros((heap_words(self.heap_size),), np.float32)
        g = self.compiled.graph
        for name in g.inputs:
            slot = self.layout[name]
            a = np.asarray(bindings[name], np.float32)
            a2 = a.reshape(slot.rows, a.shape[-1] if a.ndim else 1)
            for c in range(max(1, self.n_chips)):
                base = slot.offset + c * self.chip_stride
                view = heap[base : base + slot.rows * slot.ld]
                view = view.reshape(slot.rows, slot.ld)
                view[:, : a2.shape[1]] = a2
        return heap

    def compiled_layout_errors(self) -> List[str]:
        """What keeps this plan off the compiled TPU kernel's DMA path:
        every heap offset and row stride a descriptor hands to a tile
        DMA, and every static tile width, must be a whole number of
        ``LANE``-word tiles.  Empty when the plan can compile."""
        errs = [f"{k}={self.statics[k]} is not a multiple of {LANE}"
                for k in ("TN", "STORE_CH")
                if self.statics.get(k, LANE) % LANE]
        tk = min(LANE, max(8, self.statics["TK"]))
        if tk % LANE:
            errs.append(f"K chunk {tk} is not a multiple of {LANE}")
        if ATTN_CODE in self.descs[:, 0] and self.statics["HD"] % LANE:
            errs.append(f"head_dim {self.statics['HD']} is not a multiple "
                        f"of {LANE}")
        for row in self.descs:
            code = int(row[0])
            words = _ALIGNED_WORDS.get(code, ())
            # a one-row primary tile never adds its row stride
            if int(row[26]) > 0:
                words += (24, 25) if int(row[26]) > 1 else (24,)
            if int(row[30]) > 0:
                words += (28, 29) if int(row[30]) > 1 else (28,)
            bad = [w for w in words if row[w] >= 0 and row[w] % LANE]
            if bad:
                errs.append(f"kind {code} descriptor words {bad} = "
                            f"{[int(row[w]) for w in bad]}")
                if len(errs) > 8:
                    break
        return errs

    def read_output(self, heap: np.ndarray, name: str,
                    chip: int = 0) -> np.ndarray:
        slot = self.layout[name]
        cols = slot.shape[-1]
        base = slot.offset + chip * self.chip_stride
        view = heap[base : base + slot.rows * slot.ld]
        return view.reshape(slot.rows, slot.ld)[:, :cols].reshape(slot.shape)


#: deprecated name — the plan/executor split renamed the static half;
#: ``ops.MegakernelExecutor`` is the live half
MegakernelProgram = MegakernelPlan


#: kinds whose leading operand is a regular (m-row, descriptor-addressed)
#: tile the double-buffered pipeline can prefetch: code -> index of that
#: operand in ``op.inputs`` (the hazard analysis needs its tensor slot).
#: EMBED_LOOKUP is special-cased (a single token-id row); MOE_COMBINE and
#: noop have no regular primary tile.
_PRIMARY_ROWS_M = {
    KIND_CODES[OpKind.MATMUL]: 0,
    KIND_CODES[OpKind.RMSNORM]: 0,
    KIND_CODES[OpKind.ROPE]: 0,
    KIND_CODES[OpKind.GLU_MUL]: 0,
    KIND_CODES[OpKind.RESIDUAL_ADD]: 0,      # ELEMENTWISE shares code 5
    KIND_CODES[OpKind.ATTENTION_DECODE]: 0,
    KIND_CODES[OpKind.CACHE_UPDATE]: 1,      # the new K/V rows, not cache
    KIND_CODES[OpKind.SOFTMAX_TOPK]: 0,
    KIND_CODES[OpKind.MOE_GATHER_GEMM]: 0,
    KIND_CODES[OpKind.SSM_UPDATE]: 0,
    KIND_CODES[OpKind.CONV1D_UPDATE]: 0,
}


def _primary_record(d: np.ndarray):
    """(off, ld, rows) of a descriptor's primary operand tile, or None."""
    code = int(d[0])
    if code == KIND_CODES[OpKind.EMBED_LOOKUP]:
        # the token ids are read as scalar words at word 6; the tile only
        # paces the pipeline, so it starts at the ids' aligned lane tile
        return int(d[6]) // LANE * LANE, 1, 1
    if code in _PRIMARY_ROWS_M:
        return int(d[6]), max(1, int(d[7])), int(d[1])
    return None


def _plan_prefetch(compiled: CompiledTGraph, layout: Dict[str, TensorSlot],
                   grid: np.ndarray, num_steps: int, W: int) -> None:
    """Emit the per-worker prefetch plan (descriptor words 24-31) over the
    ``(num_steps * W, DESC_WORDS)`` grid table.

    The slot at ``(w, s)`` — a task or a padding noop — prefetches the
    primary operand tile of ``(w, s + 1)`` iff that tile cannot be
    clobbered by anything written *concurrently*: the prefetch DMA is
    issued before the stores of step ``s`` land, and on parallel hardware
    every worker's step ``s`` and ``s + 1`` tasks overlap it, so the
    source slot must be disjoint from every output slot of every task at
    steps ``s`` and ``s + 1`` on ANY worker (except the consumer itself,
    whose stores land after its reads).  At W = 1 this reduces exactly to
    the single-stream hazard rule (issuing task's own outputs only).
    Slot-interval granularity is conservative but exact under aliasing:
    layout resolves in-place state outputs to their root slots, and both
    tile reads and tile writes are contained in their tensor's slot by
    the row-padding invariant.
    """
    g = compiled.graph
    tg = compiled.tg
    part = compiled.partition

    def slot_iv(name: str):
        s = layout[name]
        return s.offset, s.offset + s.rows * s.ld

    n_rows = num_steps * W
    prim_iv = [None] * n_rows   # per grid row: primary operand slot iv
    out_ivs = [[] for _ in range(n_rows)]   # per grid row: written slots
    for tid in compiled.order:
        task = tg.tasks[tid]
        row = part.step_of[tid] * W + part.worker_of[tid]
        if task.is_dummy:
            continue
        op = g.op(task.op_id)
        code = int(grid[row, 0])
        if code == KIND_CODES[OpKind.EMBED_LOOKUP]:
            prim_iv[row] = slot_iv(op.inputs[0])
        elif code in _PRIMARY_ROWS_M:
            prim_iv[row] = slot_iv(op.inputs[_PRIMARY_ROWS_M[code]])
        out_ivs[row] = [slot_iv(name) for name in task.out_regions]

    for row in range(n_rows):
        rec = _primary_record(grid[row])
        if rec is not None:
            grid[row, 28:31] = rec

    # output intervals of one whole step (every worker) — what a prefetch
    # issued during that step may race against
    def step_out_ivs(s: int, skip_row: int = -1):
        ivs = []
        for w in range(W):
            r = s * W + w
            if r != skip_row:
                ivs.extend(out_ivs[r])
        return ivs

    for s in range(num_steps - 1):
        hazard_now = step_out_ivs(s)
        for w in range(W):
            row = s * W + w
            crow = (s + 1) * W + w
            rec = _primary_record(grid[crow])
            if rec is None:
                continue
            lo, hi = prim_iv[crow]
            hazard = hazard_now + step_out_ivs(s + 1, skip_row=crow)
            if any(wlo < hi and lo < whi for wlo, whi in hazard):
                continue                   # hazard: demand-load instead
            grid[row, 24:27] = rec
            grid[crow, 27] = 1
    # the kernel reconstructs the prefetch copies from the consumer's own
    # words 28-30 to wait on them — both sides must agree exactly
    for row in range(W, n_rows):
        if grid[row, 27] == 1:
            assert (grid[row - W, 24:27] == grid[row, 28:31]).all(), row


def _emit_events(compiled: CompiledTGraph, grid: np.ndarray, W: int
                 ) -> int:
    """Emit the wait/signal words (32-34) and return the number of
    in-heap event counters.

    Only events with at least one *cross-worker* consumer get a counter:
    a task waits on its (single, normalized) dependent event iff some
    producer runs on another worker — same-worker producers are ordered
    by the stream itself.  Every in-task of a waited event signals it, so
    the counter reaches exactly the trigger count (= ``len(in_tasks)``)
    once all producers ran; any other value observed at wait time is a
    compiler bug (counted as a violation by the kernel, asserted zero in
    the tests)."""
    tg = compiled.tg
    part = compiled.partition
    waited: set = set()
    for tid, task in tg.tasks.items():
        for eid in task.dependent_events:       # normalized: at most one
            e = tg.events[eid]
            if any(part.worker_of[p] != part.worker_of[tid]
                   for p in e.in_tasks):
                waited.add(eid)
    eidx = {eid: i for i, eid in enumerate(sorted(waited))}
    for tid, task in tg.tasks.items():
        row = part.step_of[tid] * W + part.worker_of[tid]
        for eid in task.dependent_events:
            e = tg.events[eid]
            if eid in waited and any(part.worker_of[p] != part.worker_of[tid]
                                     for p in e.in_tasks):
                grid[row, 32] = eidx[eid]
                grid[row, 33] = len(e.in_tasks)
        for eid in task.triggering_events:      # normalized: at most one
            if eid in waited:
                grid[row, 34] = eidx[eid]
    return len(eidx)


#: outputs that alias an input region (in-place state update)
_ALIAS_OPS = {
    OpKind.CACHE_UPDATE: {0: 0},      # out0 aliases ins[0] (the cache)
    OpKind.CONV1D_UPDATE: {1: 1},     # new conv state aliases ins[1]
    OpKind.SSM_UPDATE: {1: 1},        # new ssm state aliases ins[1]
}


def _build_layout(compiled: CompiledTGraph, tn: int
                  ) -> Tuple[Dict[str, TensorSlot], int]:
    g = compiled.graph
    alias: Dict[str, str] = {}
    for op in g.ops:
        amap = _ALIAS_OPS.get(op.kind)
        if amap:
            for out_i, in_i in amap.items():
                alias[op.outputs[out_i]] = op.inputs[in_i]
    layout: Dict[str, TensorSlot] = {}
    off = 0
    for name, spec in g.tensors.items():
        if name in alias:
            continue
        cols = spec.shape[-1] if spec.shape else 1
        ld = _align(cols + tn)
        rows = 1
        for s in spec.shape[:-1]:
            rows *= s
        layout[name] = TensorSlot(off, ld, tuple(spec.shape) or (1,))
        off += rows * ld
    # resolve alias chains (cache -> cache2 -> ... not chained here, but safe)
    for dst, src in alias.items():
        root = src
        while root in alias:
            root = alias[root]
        base = layout[root]
        layout[dst] = TensorSlot(base.offset, base.ld,
                                 tuple(g.spec(dst).shape))
    return layout, off + tn  # trailing pad


def lower_tgraph(compiled: CompiledTGraph, cfg,
                 tn: Optional[int] = None,
                 scheduler: str = "static",
                 trace: bool = False) -> MegakernelPlan:
    if scheduler not in ("static", "dynamic"):
        raise ValueError(f"unknown scheduler {scheduler!r}; "
                         "expected 'static' or 'dynamic'")
    g = compiled.graph
    tg = compiled.tg

    # tile-size statics from the task set
    max_n = 1
    max_m = 1
    max_k = 1
    for t in tg.tasks.values():
        if t.is_dummy:
            continue
        op = g.op(t.op_id)
        pr = t.out_regions[op.outputs[0]]
        max_m = max(max_m, pr.shape[0])
        if pr.ndim >= 2:
            max_n = max(max_n, pr.shape[-1])
        if op.kind == OpKind.MATMUL:
            max_k = max(max_k, g.spec(op.inputs[0]).shape[-1])
        if op.kind == OpKind.RMSNORM:
            max_n = max(max_n, g.spec(op.inputs[0]).shape[-1])
    tn = tn or _align(max_n)
    layout, heap_size = _build_layout(compiled, tn)

    # ---- store chunk width: the masked write-back granularity ----
    # Output tiles store TN-wide rows; to keep a tile's write-back from
    # spilling into a neighbouring column tile (tiles commute across
    # workers, so the overhang would clobber finished output), stores are
    # masked to STORE_CH-wide chunks.  STORE_CH must divide every column
    # start and every width but the last tile's of every column-tiled
    # tensor — the gcd below — so masked stores are exactly tile-wide;
    # a tensor's last tile (like a row-only tensor) only ever overhangs
    # into its row slot's padding, and what it writes there is computed
    # from zero pad columns, so the padding stays zero.
    store_ch = 128
    col_starts: Dict[str, set] = {}
    col_geom: Dict[str, list] = {}
    for t in tg.tasks.values():
        if t.is_dummy:
            continue
        op = g.op(t.op_id)
        pr = t.out_regions[op.outputs[0]]
        c0 = pr.starts[-1] if pr.ndim >= 2 else 0
        nw = pr.shape[-1] if pr.ndim >= 2 else 1
        col_starts.setdefault(op.outputs[0], set()).add(c0)
        col_geom.setdefault(op.outputs[0], []).append((c0, nw))
    for name, starts in col_starts.items():
        if len(starts) > 1:
            cols = g.spec(name).shape[-1]
            for c0, nw in col_geom[name]:
                last = c0 + nw == cols
                store_ch = math.gcd(store_ch, math.gcd(
                    c0 or store_ch, store_ch if last else nw))
    store_ch = max(1, store_ch)

    descs = np.zeros((len(compiled.order), DESC_WORDS), np.int32)
    descs[:, 32] = -1                  # wait_ev sentinel (no wait)
    descs[:, 34] = -1                  # sig_ev sentinel (no signal)
    statics: Dict[str, Any] = {
        "TN": tn, "TM": max_m, "TK": _align(max_k),
        "HD": cfg.hd, "G": cfg.q_per_kv,
        "THETA": float(cfg.rope_theta),
        "MROPE": tuple(cfg.mrope_sections or ()),
        "HD_SSM": cfg.ssm_head_dim, "N_SSM": cfg.ssm_state,
        "W_CONV": cfg.ssm_conv, "TOPK": cfg.top_k,
        "NEG_EXP_A": True,
        "EPS": cfg.norm_eps,
        "STORE_CH": store_ch,
    }

    for pos, tid in enumerate(compiled.order):
        task = tg.tasks[tid]
        d = descs[pos]
        if task.is_dummy:
            d[0] = 0
            continue
        op = g.op(task.op_id)
        kind = op.kind
        d[0] = KIND_CODES[kind]
        pr = task.out_regions[op.outputs[0]]
        out = layout[op.outputs[0]]
        ins = op.inputs
        sl = lambda i: layout[ins[i]]
        reg = lambda i: task.in_regions[ins[i]]

        r0 = pr.starts[0]
        c0 = pr.starts[-1] if pr.ndim >= 2 else 0
        m = pr.shape[0]
        n = pr.shape[-1] if pr.ndim >= 2 else 1
        d[1], d[2] = m, n
        if pr.ndim == 2:
            d[4], d[5] = out.elem(r0, c0), out.ld

        if kind == OpKind.MATMUL:
            a, w = sl(0), sl(1)
            k = a.shape[-1]
            d[3] = k
            d[6], d[7] = a.elem(r0, 0), a.ld
            d[8], d[9] = w.elem(0, c0), w.ld
            if len(ins) > 2:
                d[10] = sl(2).elem(c0)
            else:
                d[10] = -1
            d[14] = _ACT_IDS[op.attrs.get("activation")]
        elif kind == OpKind.RMSNORM:
            x, w = sl(0), sl(1)
            d[2] = x.shape[-1]
            d[6], d[7] = x.elem(r0, 0), x.ld
            d[10] = w.elem(0)
            d[14] = 1 if op.attrs.get("gemma_style") else 0
            d[17] = _fbits(op.attrs.get("eps", 1e-6))
        elif kind == OpKind.ROPE:
            x = sl(0)
            d[6], d[7] = x.elem(r0, c0), x.ld
            pos_slot = sl(1)
            psh = g.spec(ins[1]).shape
            d[19] = pos_slot.elem(r0, 0) if len(psh) == 2 else \
                pos_slot.elem(r0)
            d[20] = pos_slot.ld if len(psh) == 2 else 1
            d[15] = 1 if len(psh) == 2 else 0        # mrope positions
            d[16] = c0                               # global col offset
        elif kind in (OpKind.GLU_MUL,):
            a, bb = sl(0), sl(1)
            d[6], d[7] = a.elem(r0, c0), a.ld
            d[8], d[9] = bb.elem(r0, c0), bb.ld
            d[14] = _ACT_IDS[op.attrs.get("activation", "silu")]
        elif kind in (OpKind.RESIDUAL_ADD, OpKind.ELEMENTWISE):
            a = sl(0)
            d[6], d[7] = a.elem(r0, c0), a.ld
            if len(ins) > 1:
                bb = sl(1)
                d[8], d[9] = bb.elem(r0, c0), bb.ld
            else:
                d[8] = -1
            d[17] = _fbits(op.attrs.get("scale", 1.0))
        elif kind == OpKind.ATTENTION_DECODE:
            q, kc, vc = sl(0), sl(1), sl(2)
            b_cache, s_cache, kvd = kc.shape
            hd, grp = op.attrs["head_dim"], op.attrs["q_per_kv"]
            kv0 = c0 // (hd * grp)                   # first kv head in tile
            d[3] = s_cache
            d[6], d[7] = q.elem(r0, c0), q.ld
            d[8], d[9] = kc.elem(r0, 0, kv0 * hd), kc.ld
            d[15] = s_cache * kc.ld                  # batch stride
            d[10], d[11] = vc.elem(r0, 0, kv0 * hd), vc.ld
            d[12] = sl(3).elem(r0)                   # live_lens
            d[17] = _fbits(op.attrs.get("scale", hd ** -0.5))
            d[16] = n // (hd * grp)                  # groups in this tile
        elif kind == OpKind.CACHE_UPDATE:
            cache, new = sl(0), sl(1)
            b_cache, s_cache, kvd = cache.shape
            d[2] = task.in_regions[ins[1]].shape[-1]
            d[4], d[5] = cache.elem(r0, 0, pr.starts[-1]), cache.ld
            d[15] = s_cache * cache.ld               # batch stride
            d[6], d[7] = new.elem(r0, pr.starts[-1]), new.ld
            d[12] = sl(2).elem(r0)                   # seq_lens
        elif kind == OpKind.EMBED_LOOKUP:
            ids, table = sl(0), sl(1)
            d[6] = ids.elem(r0)
            d[8], d[9] = table.elem(0, c0), table.ld
        elif kind == OpKind.SOFTMAX_TOPK:
            x = sl(0)
            d[2] = x.shape[-1]
            d[3] = op.attrs["top_k"]
            d[6], d[7] = x.elem(r0, 0), x.ld
        elif kind == OpKind.MOE_GATHER_GEMM:
            e0 = pr.starts[0]
            toks = pr.shape[1]
            fcols = pr.shape[2]
            f0 = pr.starts[2]
            x, router, w = sl(0), sl(1), sl(2)
            d[1], d[2] = toks, fcols
            d[4], d[5] = out.elem(e0, 0, f0), out.ld
            if len(x.shape) == 3:    # second gemm: expert-local hidden
                d[6], d[7] = x.elem(e0, 0, 0), x.ld
            else:
                d[6], d[7] = x.elem(0, 0), x.ld
            d[3] = x.shape[-1]
            if len(w.shape) == 4:    # fused GLU weights (E, D, 2, F)
                d[8], d[9] = w.elem(e0, 0, 0, f0), 2 * w.ld
                d[19] = w.elem(e0, 0, 1, f0)
                d[15] = 1            # glu flag
            else:
                d[8], d[9] = w.elem(e0, 0, f0), w.ld
                d[19] = -1
                d[15] = 0
            d[10], d[11] = router.elem(0, e0), router.ld
            d[14] = _ACT_IDS[op.attrs.get("activation")]
        elif kind == OpKind.MOE_COMBINE:
            eo, router = sl(0), sl(1)
            n_exp, toks, _dm = eo.shape
            d[3] = n_exp
            d[6], d[7] = eo.elem(0, r0, c0), eo.ld
            d[15] = toks * eo.ld                     # expert stride
            d[10], d[11] = router.elem(r0, 0), router.ld
        elif kind == OpKind.SSM_UPDATE:
            x, state, dt, a_log, bmat, cmat = (sl(i) for i in range(6))
            hd = op.attrs["head_dim"]
            h0 = c0 // hd
            bsz, nh, _hd, nst = state.shape
            d[3] = nst
            d[6], d[7] = x.elem(r0, c0), x.ld
            d[8], d[9] = state.elem(r0, h0, 0, 0), state.ld
            d[15] = nh * _hd * state.ld              # batch stride (rows)
            d[16] = _hd * state.ld                   # head stride
            d[10], d[11] = dt.elem(r0, h0), dt.ld
            d[12] = a_log.elem(h0)
            d[19], d[20] = bmat.elem(r0, 0), bmat.ld
            d[21], d[22] = cmat.elem(r0, 0), cmat.ld
            d[23] = sl(6).elem(h0) if len(ins) > 6 else -1
        elif kind == OpKind.CONV1D_UPDATE:
            x, state, w = sl(0), sl(1), sl(2)
            bsz, wconv, _c = state.shape
            d[3] = wconv
            d[6], d[7] = x.elem(r0, c0), x.ld
            d[8], d[9] = state.elem(r0, 0, c0), state.ld
            d[15] = wconv * state.ld                 # batch stride
            d[10], d[11] = w.elem(0, c0), w.ld
            d[12] = sl(3).elem(c0) if len(ins) > 3 else -1
        elif kind == OpKind.ALLREDUCE:
            # single-chip lowering: an identity ALLREDUCE_CHUNK whose
            # owned span is the whole tile (the repo's TP model keeps
            # global shapes — one shard's schedule with the collective as
            # a task).  ``stamp_multichip`` replaces this placeholder
            # with the chunked-ring expansion at tp > 1.
            src = sl(0)
            assert c0 == 0 and src.ld == out.ld, \
                "allreduce tasks must span whole rows of an ld-matched pair"
            d[3] = n                         # per-row window = REAL width
            d[6], d[7] = src.elem(r0, 0), src.ld
            d[14] = 0                        # arrival mode: init
            d[15], d[16] = 0, n              # owned window = everything
            d[21], d[22], d[23] = -1, 0, 1   # peer / chunk id / count
        else:
            raise NotImplementedError(f"megakernel lowering for {kind}")

    # ---- post-pass statics from the descriptor table ----
    kinds = descs[:, 0]
    # compute-tile scratch sizing only: COMM rows stream through the sR
    # block scratch, so an atomic collective's row count (= full batch)
    # must not inflate TM
    statics["TM"] = int(descs[kinds < REMOTE_COPY_CODE, 1].max(initial=1))
    attn = kinds == KIND_CODES[OpKind.ATTENTION_DECODE]
    statics["NG"] = int(descs[attn, 16].max(initial=1))
    statics["S_MAX"] = int(descs[attn, 3].max(initial=1))
    ssm = kinds == KIND_CODES[OpKind.SSM_UPDATE]
    if ssm.any():
        statics["NH_TILE"] = int(
            (descs[ssm, 2] // max(1, cfg.ssm_head_dim)).max(initial=1))
    comb = kinds == KIND_CODES[OpKind.MOE_COMBINE]
    statics["E_MAX"] = int(descs[comb, 3].max(initial=1))
    gg = kinds == KIND_CODES[OpKind.MOE_GATHER_GEMM]
    mm = kinds == KIND_CODES[OpKind.MATMUL]
    k_max = 1
    for mask in (gg, mm):
        if mask.any():
            k_max = max(k_max, int(descs[mask, 3].max(initial=1)))
    statics["TK"] = _align(max(statics["TK"], k_max))

    part = compiled.partition
    if part is None:                   # compiled by an older pipeline
        from ...core.schedule import partition_workers
        part = partition_workers(tg, compiled.lin, 1)
        compiled.partition = part

    if scheduler == "dynamic":
        return _lower_dynamic(compiled, cfg, descs, layout, heap_size,
                              statics, part, trace)

    # ---- scatter the task table onto the (step, worker) grid ----
    W = part.num_workers
    num_steps = part.num_steps
    grid = np.zeros((num_steps * W, DESC_WORDS), np.int32)
    grid[:, 32] = -1
    grid[:, 34] = -1
    for pos, tid in enumerate(compiled.order):
        grid[part.step_of[tid] * W + part.worker_of[tid]] = descs[pos]

    # ---- event table (words 32-34), prefetch plan (words 24-31), and
    # the per-worker kernel counter blocks at the heap tail ----
    num_events = _emit_events(compiled, grid, W)
    _plan_prefetch(compiled, layout, grid, num_steps, W)
    event_offset = heap_size
    heap_size += num_events
    stats_offset = heap_size
    heap_size += STATS_WORDS * W
    statics["W"] = W
    statics["NUM_STEPS"] = num_steps
    statics["EVENT_OFF"] = event_offset
    statics["N_EVENTS"] = num_events
    statics["STATS_OFF"] = stats_offset
    ring_offset = 0
    if trace:
        # trace ring strictly after every existing region so the
        # trace-off layout is bitwise identical
        ring_offset = heap_size
        heap_size += TRACE_HEADER + num_steps * W * TRACE_WORDS
        statics["TRACE"] = 1
        statics["TR_OFF"] = ring_offset
    return MegakernelPlan(compiled, grid, layout, heap_size, statics,
                          stats_offset, W, num_steps, event_offset,
                          num_events, trace=trace,
                          ring_offset=ring_offset)


#: descriptor words holding absolute heap element offsets, per kind code
#: — the multichip stamper shifts exactly these (when >= 0; -1 marks an
#: absent optional operand) by the target chip's region base.  Words
#: 21-23 of the COMM kinds are peer/chunk metadata, NOT offsets.
_OFFSET_WORDS = {
    0: (),
    KIND_CODES[OpKind.MATMUL]: (4, 6, 8, 10),
    KIND_CODES[OpKind.RMSNORM]: (4, 6, 10),
    KIND_CODES[OpKind.ROPE]: (4, 6, 19),
    KIND_CODES[OpKind.GLU_MUL]: (4, 6, 8),
    KIND_CODES[OpKind.RESIDUAL_ADD]: (4, 6, 8),   # ELEMENTWISE shares 5
    KIND_CODES[OpKind.ATTENTION_DECODE]: (4, 6, 8, 10, 12),
    KIND_CODES[OpKind.CACHE_UPDATE]: (4, 6, 12),
    KIND_CODES[OpKind.EMBED_LOOKUP]: (4, 6, 8),
    KIND_CODES[OpKind.SOFTMAX_TOPK]: (4, 6),
    KIND_CODES[OpKind.MOE_GATHER_GEMM]: (4, 6, 8, 10, 19),
    KIND_CODES[OpKind.MOE_COMBINE]: (4, 6, 10),
    KIND_CODES[OpKind.SSM_UPDATE]: (4, 6, 8, 10, 12, 19, 21, 23),
    KIND_CODES[OpKind.CONV1D_UPDATE]: (4, 6, 8, 10, 12),
    REMOTE_COPY_CODE: (4, 6),
    AR_CHUNK_CODE: (4, 6),
}


ATTN_CODE = KIND_CODES[OpKind.ATTENTION_DECODE]

#: descriptor words, per kind code, that the compiled kernel adds to heap
#: tile DMA addresses (offsets and row strides); lengths, positions and
#: token ids are read as scalar words and may sit anywhere
_ALIGNED_WORDS = {
    KIND_CODES[OpKind.MATMUL]: (4, 5, 6, 7, 8, 9, 10),
    KIND_CODES[OpKind.RMSNORM]: (4, 5, 6, 7, 10),
    KIND_CODES[OpKind.ROPE]: (4, 5, 6, 7),
    KIND_CODES[OpKind.GLU_MUL]: (4, 5, 6, 7, 8, 9),
    KIND_CODES[OpKind.RESIDUAL_ADD]: (4, 5, 6, 7, 8, 9),
    ATTN_CODE: (4, 5, 6, 7, 8, 9, 10, 11, 15),
    KIND_CODES[OpKind.CACHE_UPDATE]: (4, 5, 6, 7, 15),
    KIND_CODES[OpKind.EMBED_LOOKUP]: (4, 5, 8, 9),
    KIND_CODES[OpKind.SSM_UPDATE]: (4, 5, 6, 7, 8, 9, 15, 16, 19, 20, 21, 22),
    KIND_CODES[OpKind.CONV1D_UPDATE]: (4, 5, 6, 7, 8, 9, 10, 11, 12, 15),
}


def _noop_row() -> np.ndarray:
    d = np.zeros(DESC_WORDS, np.int32)
    d[32] = -1
    d[34] = -1
    return d


def _comm_desc(t, d0: np.ndarray, c: int, stage_sz: int, sbase: int,
               ebase: int, chip_stride: int, n_chips: int) -> np.ndarray:
    """Lower one :class:`~...distributed.comm_tasks.CommTask` of the
    placeholder ``d0``'s ring expansion to a descriptor row for chip
    ``c``.  The moved unit is a per-row column window (``m`` rows of the
    placeholder's tile, real width chunked — pad columns never enter the
    ring).  ``sbase`` is the collective's staging base (2 packed phase
    buffers of ``stage_sz`` words per chip); ``ebase`` its comm-event
    base index."""
    from ...distributed.comm_tasks import MODE_INIT
    d = _noop_row()
    m, out_ld, src_ld = int(d0[1]), int(d0[5]), int(d0[7])
    out0 = int(d0[4]) + c * chip_stride      # chip c's output tile base
    src0 = int(d0[6]) + c * chip_stride      # chip c's input tile base
    stage = lambda chip, phase: sbase + (chip * 2 + phase) * stage_sz
    d[1] = m
    d[21], d[22], d[23] = t.peer, t.chunk, n_chips
    if t.kind == "init":
        d[0] = AR_CHUNK_CODE
        d[14] = MODE_INIT
        d[3] = t.nwords
        d[4], d[5] = out0, out_ld
        d[6], d[7] = src0, src_ld
        d[15], d[16] = t.own_start, t.own_len
    elif t.kind == "send":
        d[0] = REMOTE_COPY_CODE
        d[3] = t.nwords
        d[6], d[7] = out0 + t.start, out_ld
        d[4], d[5] = stage(t.peer, t.phase), t.nwords   # packed staging
        d[10] = t.peer                       # comm semaphore lane
        d[34] = ebase + t.sig_ev             # peer's arrival event
    else:                                    # recv (accumulate / store)
        d[0] = AR_CHUNK_CODE
        d[14] = t.mode
        d[3] = t.nwords
        d[6], d[7] = stage(c, t.phase), t.nwords
        d[4], d[5] = out0 + t.start, out_ld
        d[32], d[33] = ebase + t.wait_ev, 1
    return d


def stamp_multichip(plan: MegakernelPlan, n_chips: int) -> MegakernelPlan:
    """Stamp a single-chip static plan into a ``C``-chip fused-transport
    plan (paper §6.5 + Event Tensor's comm-as-tasks).

    The descriptor grid is replicated per chip — worker lane ``c*W + w``
    is chip ``c``'s worker ``w``; every heap offset shifts by the chip's
    region base and every event id by the chip's event block — and each
    ``ALLREDUCE`` placeholder step is replaced by the
    ``comm_tasks.expand_ring_allreduce`` sequence over the tile's REAL
    row width (chunks are per-row column windows — pad columns stay out
    of the ring) inserted as full-width grid steps: at inserted step
    ``t`` every chip runs its ring task of relative step ``t`` on the
    worker lane that owned the placeholder (all other lanes pad with
    noops).  Because all chips' expansions are
    step-aligned and every receive's matching send sits at a strictly
    earlier relative step, the stamped grid stays dependency-safe under
    step-major execution — the kernel's event-wait violation counter
    (asserted zero) checks exactly this.

    The "chips" are a lowering concept: the stamped plan still executes
    as ONE ``pallas_call`` whose heap concatenates the per-chip tensor
    regions (the *fused transport*), so the whole TP group remains a
    single megakernel and CPU CI exercises the full protocol.  On real
    multi-chip hardware the same descriptors drive
    ``pltpu.make_async_remote_copy`` against the peer's heap instead
    (the gated ``REMOTE_DMA`` path in ``kernel.py``) — only the
    transport changes, never the task table.

    Prefetch plan: a slot's words 24-26 must describe its stream
    successor, so the pre-insertion predecessor's prefetch moves onto
    the LAST inserted row of each worker lane (safe: any consumer whose
    primary tile overlaps the collective's output was already hazard-
    blocked to a demand load by the base plan, and ring writes touch
    only the collective's span and the staging region).
    """
    from ...distributed.comm_tasks import (expand_ring_allreduce,
                                           n_comm_events, n_ring_steps)
    assert plan.scheduler == "static", \
        "multichip stamping requires the static scheduler"
    C = n_chips
    if C <= 1:
        return plan
    W = plan.num_workers
    S0 = plan.num_steps
    Wt = C * W
    grid0 = plan.descs
    chip_stride = plan.event_offset          # words per chip region
    nev0 = plan.num_events

    # collectives in (step, worker) order; staging + comm-event bases
    colls = [(s, w) for s in range(S0) for w in range(W)
             if grid0[s * W + w, 0] == AR_CHUNK_CODE]
    event_off = C * chip_stride
    n_comm_ev = len(colls) * n_comm_events(C)
    stage_bases: Dict[Tuple[int, int], int] = {}
    stage_szs: Dict[Tuple[int, int], int] = {}
    ev_bases: Dict[Tuple[int, int], int] = {}
    cursor = event_off + C * nev0 + n_comm_ev
    for i, (s, w) in enumerate(colls):
        # packed per-(chip, phase) staging: m rows x the widest chunk of
        # the collective's REAL row width (pad cols never hit the wire)
        d0 = grid0[s * W + w]
        stage_szs[(s, w)] = int(d0[1]) * -(-int(d0[2]) // C)
        stage_bases[(s, w)] = cursor
        cursor += 2 * C * stage_szs[(s, w)]
        ev_bases[(s, w)] = C * nev0 + i * n_comm_events(C)

    def stamp_row(row: np.ndarray, c: int) -> np.ndarray:
        d = row.copy()
        for wd in _OFFSET_WORDS[int(d[0])]:
            if d[wd] >= 0:
                d[wd] += c * chip_stride
        if d[26] > 0:                        # prefetch plan source
            d[24] += c * chip_stride
        if d[30] > 0:                        # own primary record
            d[28] += c * chip_stride
        if d[32] >= 0:
            d[32] += c * nev0
        if d[34] >= 0:
            d[34] += c * nev0
        return d

    blocks: List[np.ndarray] = []
    for s in range(S0):
        ph = {w: grid0[s * W + w] for w in range(W)
              if grid0[s * W + w, 0] == AR_CHUNK_CODE}
        if not ph:
            block = np.zeros((Wt, DESC_WORDS), np.int32)
            for c in range(C):
                for w in range(W):
                    block[c * W + w] = stamp_row(grid0[s * W + w], c)
            blocks.append(block)
            continue
        K = n_ring_steps(C)
        ring: Dict[Tuple[int, int, int], np.ndarray] = {}
        for w, d0 in ph.items():
            for t in expand_ring_allreduce(int(d0[2]), C):
                ring[(w, t.chip, t.step)] = _comm_desc(
                    t, d0, t.chip, stage_szs[(s, w)],
                    stage_bases[(s, w)], ev_bases[(s, w)],
                    chip_stride, C)
        for ti in range(K):
            block = np.tile(_noop_row(), (Wt, 1))
            for c in range(C):
                for w in range(W):
                    lane = c * W + w
                    if w in ph:
                        row = ring[(w, c, ti)].copy()
                        d0 = ph[w]
                        if ti == 0 and d0[32] >= 0:
                            # init inherits the placeholder's wait
                            row[32] = d0[32] + c * nev0
                            row[33] = d0[33]
                        if ti == K - 1:
                            # the final store inherits the placeholder's
                            # consumer signal and its moved prefetch
                            if d0[34] >= 0:
                                row[34] = d0[34] + c * nev0
                            if d0[26] > 0:
                                row[24:27] = d0[24:27]
                                row[24] += c * chip_stride
                        block[lane] = row
                    elif ti == 0:
                        row = stamp_row(grid0[s * W + w], c)
                        row[24:27] = 0       # moved to the last block
                        block[lane] = row
                    elif ti == K - 1:
                        src = grid0[s * W + w]
                        if src[26] > 0:
                            row = block[lane].copy()
                            row[24:27] = src[24:27]
                            row[24] += c * chip_stride
                            block[lane] = row
            blocks.append(block)

    grid = np.concatenate(blocks).astype(np.int32)
    S = len(blocks)
    # re-assert the prefetch pair invariant on the stamped grid: a
    # consumer's own record must equal its stream predecessor's plan
    for row in range(Wt, S * Wt):
        if grid[row, 27] == 1:
            assert (grid[row - Wt, 24:27] == grid[row, 28:31]).all(), row

    stats_off = cursor
    heap_size = stats_off + STATS_WORDS * Wt
    statics = dict(plan.statics)
    ring_off = 0
    if plan.trace:
        ring_off = heap_size
        heap_size += TRACE_HEADER + S * Wt * TRACE_WORDS
        statics["TRACE"] = 1
        statics["TR_OFF"] = ring_off
    # +256: the comm span copies run in 256-word masked blocks, so the
    # last block of a span may read (never write) past its end
    heap_size += 256
    statics.update({"W": Wt, "NUM_STEPS": S, "EVENT_OFF": event_off,
                    "N_EVENTS": C * nev0 + n_comm_ev,
                    "STATS_OFF": stats_off, "N_CHIPS": C})
    return MegakernelPlan(plan.compiled, grid, plan.layout, heap_size,
                          statics, stats_off, Wt, S, event_off,
                          C * nev0 + n_comm_ev, n_chips=C,
                          chip_stride=chip_stride, trace=plan.trace,
                          ring_offset=ring_off)


def _lower_dynamic(compiled: CompiledTGraph, cfg, descs: np.ndarray,
                   layout: Dict[str, TensorSlot], heap_size: int,
                   statics: Dict[str, Any], part,
                   trace: bool = False) -> MegakernelPlan:
    """Finish the lowering for ``scheduler="dynamic"``: keep the flat
    per-task table in linearized order (row id == lin position — the pop
    priority), stamp every row's event wait/signal words + affinity, and
    append the ready-queue regions to the heap.  No prefetch plan: which
    task a slot runs is a runtime decision, so every task demand-loads
    its primary tile through its own record (words 28-30) — the cost the
    ``mpk_dyn`` simulator charges as the per-pop queue overhead."""
    from ...runtime.dyn_sched import QUEUE_CAP, build_dyn_sched

    dyn = build_dyn_sched(compiled, part)
    W = dyn.num_workers
    T = dyn.num_tasks
    assert descs.shape[0] == T

    for row in range(T):
        rec = _primary_record(descs[row])
        if rec is not None:
            descs[row, 28:31] = rec
        descs[row, 35] = dyn.affinity[row]
        e = int(dyn.wait_ev[row])
        if e >= 0:
            descs[row, 32] = e
            descs[row, 33] = dyn.trigger[e]
        descs[row, 34] = dyn.sig_ev[row]

    num_steps = -(-T // W)             # pop slots per worker lane
    event_offset = heap_size
    heap_size += dyn.num_events
    queue_offset = heap_size
    heap_size += W * QUEUE_CAP + dyn.overflow_cap
    qc_offset = heap_size
    heap_size += 2 * (W + 1)
    trace_offset = heap_size
    heap_size += num_steps * W
    stats_offset = heap_size
    heap_size += STATS_WORDS * W
    ring_offset = 0
    if trace:
        ring_offset = heap_size
        heap_size += TRACE_HEADER + num_steps * W * TRACE_WORDS

    statics.update({
        "W": W, "NUM_STEPS": num_steps, "EVENT_OFF": event_offset,
        "N_EVENTS": dyn.num_events, "STATS_OFF": stats_offset,
        "DYN": 1, "QOFF": queue_offset, "QCAP": QUEUE_CAP,
        "OV_ROWS": dyn.overflow_cap // QUEUE_CAP, "QC_OFF": qc_offset,
        "TRACE_OFF": trace_offset, "T_TASKS": T,
        "MAX_OUT": dyn.max_out,
    })
    if trace:
        statics["TRACE"] = 1
        statics["TR_OFF"] = ring_offset
    return MegakernelPlan(compiled, descs, layout, heap_size, statics,
                          stats_offset, W, num_steps, event_offset,
                          dyn.num_events, scheduler="dynamic", dyn=dyn,
                          queue_offset=queue_offset, qc_offset=qc_offset,
                          trace_offset=trace_offset, trace=trace,
                          ring_offset=ring_offset)
