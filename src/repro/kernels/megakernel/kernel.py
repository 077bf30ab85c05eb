"""The persistent megakernel: one ``pl.pallas_call`` executes an entire
compiled tGraph as W decentralized per-worker task streams — statically
partitioned (``scheduler="static"``) or dispatched at runtime from
heap-resident ready queues (``scheduler="dynamic"``).

TPU adaptation of MPK's in-kernel runtime (paper §5): the grid is 2-D
``(step, worker)`` — each worker walks its own static descriptor stream
(the compiler's makespan-minimizing partition of the linearized order),
synchronized against the other workers through an **event-counter table
resident in the heap**; task descriptors are scalar-prefetched into SMEM
(§5.3 descriptor prefetch); operand tiles are DMA'd HBM→VMEM as *bulk
strided tiles* (one logical DMA per tile — issued as back-to-back row
copies against one semaphore, which a real TPU DMA engine expresses as a
single strided descriptor); state updates (KV-cache / conv / SSM) write
in place through buffer aliasing.  Task dispatch is a ``lax.switch``
over the task-kind word — the task library below is the §4.2 per-task
device-function set.

Event synchronization (paper §5.1): a task whose producers run on other
workers carries wait-words (event index + trigger count, descriptor
words 32-33) and checks the in-heap counter before compute; after its
stores land it increments its signal event (word 34).  Interpret mode
executes the grid sequentially step-major (worker-fastest), an order the
compiler proved dependency-safe, so the spin-wait of real parallel
hardware degrades to a *checked assertion*: a counter that does not
already equal its trigger count is a compiler bug, counted in the stats
block as an event-wait violation (asserted zero by the tests).

Cross-task software pipelining (paper §5, Fig. 12): every grid step runs
two phases against each worker's double-buffered primary-operand tile
``sP[w]`` of shape (2, TM, TN):

* **prefetch phase** — issue async loads for the NEXT task in this
  worker's stream (descriptor words 24-26, emitted by the compiler's
  per-worker prefetch plan in ``desc.py``) into the B side
  ``sP[w, (s+1) % 2]``, tracked by the per-worker per-slot DMA semaphore
  ``psem[w, (s+1) % 2]``; the copies overlap this step's compute.
  Padding noop slots still run this phase, keeping the buffer warm
  across a worker's idle steps.
* **compute phase** — wait on ``psem[w, s % 2]`` and consume the A side
  ``sP[w, s % 2]`` (words 27-30 carry the task's own primary record so
  the kernel never decodes two descriptors per step).  Tasks whose
  operand could not be prefetched (hazard with a concurrent step's
  writes, or the first task of a stream) demand-load the tile instead.

Interpret mode copies at ``start()`` (verified), so the prefetch genuinely
reads memory *before* the surrounding stores land — the compiler's
cross-worker hazard analysis is load-bearing and is exercised by the
bitwise parity suite, exactly as on hardware.

A per-worker counter block (``STATS_WORDS`` f32 words per worker at
``statics["STATS_OFF"]`` in the heap) is maintained by the kernel
itself: [0] bulk tile DMAs issued, [1] row copies inside them (what the
pre-pipelining kernel issued as individual DMAs), [2] prefetch tiles
issued, [3] primary tiles demand-loaded, [5] event waits checked,
[6] event-wait violations, [7] event signals, [8-11] dynamic-scheduler
pops (own pool / overflow / steals / idle slots).
``MegakernelExecutor.pipeline_counters()`` / ``worker_counters()`` read
it back.

Dynamic scheduler (``statics["DYN"] == 1``; protocol in
``runtime/dyn_sched.py``): each grid slot runs **pop → wait-check →
compute → signal-and-enqueue** instead of walking a static stream.  The
pop scans this worker's 128-word ready pool (one bulk row DMA + a VPU
argmin — the minimum descriptor-row id wins, making the pop priority
"earliest linearized position" with the task-id tie-break inherent),
falling back to the shared overflow queue and then to *stealing* from
the other workers' pools.  The popped row indexes the schedule-order-
free descriptor table dynamically (scalar-prefetched SMEM).  After the
task's stores land, the signal RMW increments its event counter; the
producer that brings it to the trigger count walks the event's consumer
list (the second scalar-prefetch operand) and pushes each newly-ready
row into its affinity worker's pool (first empty slot, overflow when
full).  Every slot also records what it popped into the in-heap pop
trace, which the tests assert equal to ``dyn_sched.replay_sequential``
— the sequential interpret-mode execution *is* the protocol replay, so
dynamic outputs stay bitwise-identical to the static scheduler and the
interpreter.  No cross-slot prefetch is planned (the next task is a
runtime decision): every task demand-loads its primary tile through its
own record, words 28-30.

Compiled for a TPU (``interpret=False``, Mosaic): the heap is one
``(1, N)`` f32 row, tiled ``(1, 128)`` in HBM.  Every tile moves as
single-row DMAs between it and ``(rows, 1, width)`` VMEM buffers —
Mosaic slices neither a 1-D HBM array nor an ``(8, 128)``-tiled VMEM
buffer by one row — so every DMA offset, row stride and width is a whole
number of 128-word tiles (``MegakernelPlan.compiled_layout_errors``).
Scalar words (lengths, token ids, positions, counters, event and trace
words) never pass through vector registers: they move through a 256-word
SMEM block staged from the word's tile.  The grid runs in order on the
chip's one TensorCore, exactly as in interpret mode.  The compiled kernel
covers the static scheduler and the dense decoder's and the SSM's kinds
(``COMPILED_KINDS``); MoE and COMM kinds and the dynamic scheduler run
in interpret mode only.

Validated in interpret mode against the numpy tGraph interpreter and the
JAX model oracle (tests/test_megakernel.py, tests/test_program_api.py,
tests/test_workers.py for W > 1); compiled, by ``chip_smoke.py`` against
the f32 reference on a TPU v5e and by tests/test_chip_compile.py against
a described one.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Iterable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...runtime.dyn_sched import QUEUE_EMPTY
from .desc import (LANE, STATS_WORDS, TRACE_HEADER, TRACE_WORDS,
                   heap_words)

__all__ = ["make_megakernel", "make_count", "COMM_BLOCK", "COMPILED_KINDS"]

#: occupied/empty discriminator for ready-pool slots (row ids sit far
#: below, the QUEUE_EMPTY sentinel far above)
_QTH = QUEUE_EMPTY / 2

#: word width of one COMM span-copy block: the chunked collectives
#: (kinds 14/15) move flat heap spans in masked 256-word blocks through
#: the ``sR`` scratch — the stamper reserves a trailing heap pad so the
#: last block of a span may read (never write) past its end
COMM_BLOCK = 256

#: scalar words (counters, event and tick words, token ids, lengths,
#: positions, trace records) move through a 256-word SMEM block staged
#: from the 128-aligned tile holding the word — any span of up to 128
#: words starting in that tile fits
_BLK = 2 * LANE

#: task kinds the compiled (Mosaic) kernel implements: the dense decoder
#: path plus the SSM and conv updates.  A plan holding any other kind
#: runs only in interpret mode.
COMPILED_KINDS = frozenset(range(9)) | {12, 13}

#: incremented on every ``make_megakernel`` call — the compile-count hook
#: used by tests to assert the Program API builds the kernel exactly once
#: across an N-step decode loop
_MAKE_COUNT = 0


def make_count() -> int:
    return _MAKE_COUNT


def _f32(bits):
    """Reinterpret an int32 descriptor word as an f32 (1, 1) vector (the
    scalar core has no bitcast)."""
    return jax.lax.bitcast_convert_type(
        jnp.full((1, 1), bits, jnp.int32), jnp.float32)


def _act(y, act_id):
    return jax.lax.switch(
        act_id,
        [lambda v: v, jax.nn.silu, jax.nn.gelu],
        y,
    )


def _dot(a, b, dims):
    # the heap is f32 and the kernel is held to a plain f32 reference:
    # ask the MXU for full f32 contraction, not a bf16 pass
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def make_megakernel(statics: Dict[str, Any], num_steps: int,
                    heap_size: int, *, interpret: bool,
                    kinds: Iterable[int] = ()):
    """Build the persistent kernel over a ``(1, heap_words(heap_size))``
    f32 heap.

    ``interpret=True`` runs it through the Pallas interpreter (CPU);
    ``False`` compiles it with Mosaic for a TPU, which takes a static
    scheduler plan whose task kinds are all in ``COMPILED_KINDS``.
    ``kinds`` lists the task-kind codes the descriptor table holds;
    dispatch branches for other kinds are left out of the kernel (empty
    means every kind).
    """
    global _MAKE_COUNT
    _MAKE_COUNT += 1
    kinds = frozenset(int(k) for k in kinds) or frozenset(range(16))
    if not interpret:
        if statics.get("DYN", 0):
            raise NotImplementedError(
                "the compiled TPU megakernel runs the static scheduler only")
        if not kinds <= COMPILED_KINDS:
            raise NotImplementedError(
                "task kinds %s have no compiled TPU implementation yet"
                % sorted(kinds - COMPILED_KINDS))
    W = max(1, statics.get("W", 1))
    EVENT_OFF = statics.get("EVENT_OFF", 0)
    TN = statics["TN"]
    TM = statics["TM"]
    TKC = min(128, max(8, statics["TK"]))
    KCH = max(1, math.ceil(statics["TK"] / TKC))
    HD = max(1, statics["HD"])
    G = max(1, statics["G"])
    NG = max(1, statics.get("NG", 1))
    S_MAX = max(1, statics.get("S_MAX", 1))
    TS = min(128, S_MAX)
    SCH = max(1, math.ceil(S_MAX / TS))
    MROPE = statics["MROPE"]
    THETA = statics["THETA"]
    HDS = max(1, statics["HD_SSM"])
    NS = max(1, statics["N_SSM"])
    NHT = max(1, statics.get("NH_TILE", 1))
    WC = max(1, statics["W_CONV"])
    TOPK = max(1, statics["TOPK"])
    EMAX = max(1, statics.get("E_MAX", 1))
    STATS_OFF = statics["STATS_OFF"]
    SCH_W = max(1, statics.get("STORE_CH", 128))   # masked-store chunk
    SB_ROWS = max(TKC, TS, HDS, WC, 8)
    TNK = max(TN, TKC)
    DYN = bool(statics.get("DYN", 0))
    QOFF = statics.get("QOFF", 0)
    QCAP = statics.get("QCAP", 128)
    OV_ROWS = max(1, statics.get("OV_ROWS", 1))
    OVOFF = QOFF + W * QCAP
    QC_OFF = statics.get("QC_OFF", 0)
    TRACE_OFF = statics.get("TRACE_OFF", 0)
    MAX_OUT = statics.get("MAX_OUT", 0)
    #: real multi-chip transport: when set, REMOTE_COPY descriptors
    #: issue ``pltpu.make_async_remote_copy`` against the PEER chip's
    #: heap (one program instance per chip under shard_map) instead of
    #: the fused in-heap copy.  Requires actual TPU devices — CPU/
    #: interpret CI always runs the fused transport (N_CHIPS regions in
    #: one heap), which executes the identical task table.
    RDMA = bool(statics.get("REMOTE_DMA", 0))
    TRACE = bool(statics.get("TRACE", 0))
    TR_OFF = statics.get("TR_OFF", 0)
    HEAP = heap_words(heap_size)

    def kernel(desc, *rest):
        rest = list(rest)
        sT = rest.pop() if TRACE else None   # trace-record staging
        if DYN:
            (sched, heap_in, heap, sA, sB, sC, sD, acc, acc2, sO, sY, sP,
             sBlk, cnt, sR, sem, psem, rsend, rrecv, sQ, sS) = rest
        else:
            (heap_in, heap, sA, sB, sC, sD, acc, acc2, sO, sY, sP,
             sBlk, cnt, sR, sem, psem, rsend, rrecv) = rest
        s = pl.program_id(0)                # grid step (shared time axis)
        w_id = pl.program_id(1)             # worker lane
        slot = jax.lax.rem(s, 2)            # A side: this step's operands
        nslot = jax.lax.rem(s + 1, 2)       # B side: prefetch target

        @pl.when(s == 0)
        def _():                            # each worker zeroes its row
            for j in range(STATS_WORDS):
                cnt[w_id, j] = jnp.float32(0.0)

        def cadd(j, v):
            """Accumulate into this worker's counter row."""
            cnt[w_id, j] = cnt[w_id, j] + v

        def _count(nrows):
            """One bulk tile DMA moving ``nrows`` strided rows.  The row
            total spills into a 2^20-unit high word so the f32 counters
            stay exact far past 2^24 rows/launch (full-size models)."""
            cadd(0, 1.0)
            cadd(1, jnp.asarray(nrows).astype(jnp.float32))

            @pl.when(cnt[w_id, 1] >= 1048576.0)
            def _():
                cadd(4, 1.0)
                cadd(1, -1048576.0)

        def _count_tile(nrows):
            """``_count`` for a bulk tile DMA that may move no rows."""
            @pl.when(nrows > 0)
            def _():
                _count(nrows)

        # ---------------- heap views ----------------------------------
        # The heap is one (1, N) f32 row.  Row-tile operands move between
        # it and (rows, 1, width) VMEM buffers one (1, width) row at a
        # time; the compiled kernel needs every such offset and width to
        # be a whole number of 128-word tiles (checked at lowering).
        def hrow(off, width):
            return heap.at[:, pl.ds(pl.multiple_of(off, LANE), width)]

        def vrow(buf, i, width, c0=0):
            return buf.at[i, :, pl.ds(c0, width)]

        def mat(buf, nrows, width):
            """Rows ``[0, nrows)`` of a (rows, 1, W) buffer as a value."""
            return buf[:nrows, :, :width].reshape(nrows, width)

        def store_out(y, m, valid):
            """Stage a (TM, TN) result in ``sO`` and store its first ``m``
            rows to the task's output tile (words 4-5)."""
            sO[...] = y.reshape(TM, 1, TN)
            store_tile(sO, d(4), d(5), m, TM, TN, valid=valid)

        def _copy(src, dst):
            cp = pltpu.make_async_copy(src, dst, sem)
            cp.start()
            cp.wait()

        # ---------------- scalar words through the SMEM block --------
        def blk_load(addr):
            """Stage the heap words from ``addr``'s tile into ``sBlk``;
            returns ``addr``'s lane in the block."""
            base = addr - jax.lax.rem(addr, LANE)
            _copy(hrow(base, _BLK), sBlk)
            return addr - base

        def blk_store(addr):
            base = addr - jax.lax.rem(addr, LANE)
            _copy(sBlk, hrow(base, _BLK))

        def peek(addr):
            return sBlk[0, blk_load(addr)]

        def poke(addr, value):
            sBlk[0, blk_load(addr)] = value
            blk_store(addr)

        def poke_add(addr, delta):
            """heap[addr] += delta; returns the new value.  The grid runs
            in order on one core, so the read-modify-write is exact."""
            o = blk_load(addr)
            new = sBlk[0, o] + delta
            sBlk[0, o] = new
            blk_store(addr)
            return new

        # ---------------- DMA helpers (all through the aliased out ref) ---
        def load_tile(dst, base, ld, nrows, max_rows, width):
            """dst[i, :width] = heap[base + i*ld : +width], zero if i>=nrows.

            ONE bulk strided DMA: every row copy is issued back-to-back
            (start), then completion is awaited once."""
            nrows = jnp.asarray(nrows, jnp.int32)
            _count_tile(nrows)

            def start_body(i, _):
                @pl.when(i < nrows)
                def _():
                    pltpu.make_async_copy(
                        hrow(base + i * ld, width),
                        vrow(dst, i, width), sem).start()
                return 0
            jax.lax.fori_loop(0, max_rows, start_body, 0)

            def fin_body(i, _):
                @pl.when(i < nrows)
                def _():
                    pltpu.make_async_copy(
                        hrow(base + i * ld, width),
                        vrow(dst, i, width), sem).wait()
                @pl.when(jnp.logical_not(i < nrows))
                def _():
                    dst[i, :, pl.ds(0, width)] = jnp.zeros((1, width),
                                                           jnp.float32)
                return 0
            jax.lax.fori_loop(0, max_rows, fin_body, 0)

        def _chunked(fn, width, valid):
            """Call ``fn(c0, cw)`` for each column chunk a store writes:
            the whole width, or — masked by ``valid`` — the STORE_CH-wide
            chunks whose start lies inside the task's true output width.
            A tile's write-back must never spill into a neighbouring
            column tile of the same tensor: tiles commute across workers,
            so an overhanging store would clobber a neighbour's finished
            output.  Chunk starts are aligned exactly like the
            decomposer's column tiles, so masked stores are disjoint
            between tiles (the tail chunk only ever overhangs into the
            row slot's zero padding)."""
            if valid is None:
                fn(0, width)
                return
            chw = min(SCH_W, width)
            nch = -(-width // chw)
            live = jnp.minimum(nch, (jnp.asarray(valid) + chw - 1) // chw)

            def body(j, _):
                fn(pl.multiple_of(j * chw, chw), chw)
                return 0
            jax.lax.fori_loop(0, live, body, 0)

        def store_tile(src, base, ld, nrows, max_rows, width, valid=None):
            """Bulk strided write-back: issue all row copies, wait once;
            chunk-masked by ``valid`` (see ``_chunked``)."""
            nrows = jnp.asarray(nrows, jnp.int32)
            _count_tile(nrows)

            def row_copies(i, op):
                _chunked(lambda c0, cw: getattr(pltpu.make_async_copy(
                    vrow(src, i, cw, c0), hrow(base + i * ld + c0, cw),
                    sem), op)(), width, valid)

            def start_body(i, _):
                @pl.when(i < nrows)
                def _():
                    row_copies(i, "start")
                return 0
            jax.lax.fori_loop(0, max_rows, start_body, 0)

            def fin_body(i, _):
                @pl.when(i < nrows)
                def _():
                    row_copies(i, "wait")
                return 0
            jax.lax.fori_loop(0, max_rows, fin_body, 0)

        def load_row(dst, row, base, width):
            """Single contiguous row: heap[base:+width] -> dst[row]."""
            _count(1)
            _copy(hrow(base, width), vrow(dst, row, width))

        def load_col(row, base, stride, nelems, max_elems):
            """Strided element gather (one bulk DMA of ``nelems`` width-1
            rows): sC[row, i] = heap[base + i*stride], zero if i>=nelems.
            Single-word copies: interpret mode only."""
            nelems = jnp.asarray(nelems, jnp.int32)
            _count_tile(nelems)

            def one(i):
                return pltpu.make_async_copy(
                    heap.at[:, pl.ds(base + i * stride, 1)],
                    sC.at[row, :, pl.ds(i, 1)], sem)

            def body(i, _):
                @pl.when(i < nelems)
                def _():
                    one(i).start()
                return 0
            jax.lax.fori_loop(0, max_elems, body, 0)

            def fin(i, _):
                @pl.when(i < nelems)
                def _():
                    one(i).wait()
                @pl.when(jnp.logical_not(i < nelems))
                def _():
                    sC[row, :, pl.ds(i, 1)] = jnp.zeros((1, 1), jnp.float32)
                return 0
            jax.lax.fori_loop(0, max_elems, fin, 0)

        def store_row_vec(buf, row, base, width, valid=None):
            """Single-row store, chunk-masked like ``store_tile``."""
            _count(1)
            _chunked(lambda c0, cw: _copy(vrow(buf, row, cw, c0),
                                          hrow(base + c0, cw)),
                     width, valid)

        def store_primary_row(r, base, valid):
            """Write one row of the primary tile back to the heap (the
            cache-update path stores prefetched K/V rows directly),
            chunk-masked to the new rows' true width."""
            _count(1)
            _chunked(lambda c0, cw: _copy(
                sP.at[w_id, slot, r, :, pl.ds(c0, cw)], hrow(base + c0, cw)),
                TN, valid)

        # --------------- queue-word helpers (dynamic scheduler only) ----
        # Raw single-row / single-word heap traffic for the ready pools,
        # cursor counters and pop trace.  Deliberately NOT routed through
        # ``_count``: the DMA counters keep measuring operand traffic;
        # scheduler traffic is visible through words 8-11 instead.
        def _qrow(r, base, width=QCAP):
            """sQ[r, :width] = heap[base : base + width] (one pool row)."""
            _copy(heap.at[:, pl.ds(base, width)], vrow(sQ, r, width))

        # --------------- task selection: static grid row or dynamic pop
        if DYN:
            # Pop per the protocol order (runtime/dyn_sched.py): own
            # pool, overflow queue, then steal.  Each pool scan is one
            # bulk row DMA + a vector argmin; the minimum row id (=
            # earliest linearized position) wins.
            sS[0] = jnp.int32(-1)       # source: -1 none, 0 own, 1 ovf,
            sS[1] = jnp.int32(0)        #   2 steal; [1] consumed slot
            sS[4] = jnp.int32(0)        #   offset; [4] popped row id
            _qrow(0, QOFF + w_id * QCAP)
            vals = sQ[0, 0, :]
            mn = jnp.min(vals)

            @pl.when(mn < _QTH)
            def _():
                sS[0] = jnp.int32(0)
                sS[1] = (w_id * QCAP
                         + jnp.argmin(vals)).astype(jnp.int32)
                sS[4] = mn.astype(jnp.int32)

            @pl.when(sS[0] < 0)
            def _():                    # overflow queue scan
                for r in range(OV_ROWS):
                    _qrow(r, OVOFF + r * QCAP)
                tile = sQ[:OV_ROWS, 0, :]
                mo = jnp.min(tile)

                @pl.when(mo < _QTH)
                def _():
                    sS[0] = jnp.int32(1)
                    sS[1] = (W * QCAP + jnp.argmin(
                        tile.reshape(-1))).astype(jnp.int32)
                    sS[4] = mo.astype(jnp.int32)

            for k in range(1, W):       # steal scan, victims (w+k) % W
                @pl.when(sS[0] < 0)
                def _(k=k):
                    vw = jax.lax.rem(w_id + k, W)
                    _qrow(0, QOFF + vw * QCAP)
                    sv = sQ[0, 0, :]
                    ms = jnp.min(sv)

                    @pl.when(ms < _QTH)
                    def _():
                        sS[0] = jnp.int32(2)
                        sS[1] = (vw * QCAP
                                 + jnp.argmin(sv)).astype(jnp.int32)
                        sS[4] = ms.astype(jnp.int32)

            popped = sS[0] >= 0
            t = sS[4]                   # the popped descriptor row

            @pl.when(popped)
            def _():                    # consume: mark slot empty
                poke(QOFF + sS[1], jnp.float32(QUEUE_EMPTY))
                pool = jnp.minimum(sS[1] // QCAP, W)
                poke_add(QC_OFF + 2 * pool + 1, 1.0)   # popped cursor

                @pl.when(sS[0] == 0)
                def _():
                    cadd(8, 1.0)

                @pl.when(sS[0] == 1)
                def _():
                    cadd(9, 1.0)

                @pl.when(sS[0] == 2)
                def _():
                    cadd(10, 1.0)

            @pl.when(jnp.logical_not(popped))
            def _():                    # only the trailing pad slots
                cadd(11, 1.0)

            # pop trace: what this grid slot executed (QUEUE_EMPTY when
            # idle) — asserted equal to dyn_sched.replay_sequential
            poke(TRACE_OFF + s * W + w_id,
                 jnp.where(popped, sS[4].astype(jnp.float32),
                           jnp.float32(QUEUE_EMPTY)))
        else:
            popped = None
            t = s * W + w_id            # row in the static grid
        d = lambda i: desc[t, i]

        # ------------------- trace ring (CompileOptions.trace) ---------
        # Logical clock: one in-heap tick word at TR_OFF, fetch-and-
        # incremented at slot start and slot end (exact under the
        # in-order grid; on parallel hardware this is a global atomic).
        # The record is staged word by word in the sT SMEM scratch.
        if TRACE:
            def _tick(col):
                o = blk_load(TR_OFF)
                sT[col] = sBlk[0, o]
                sBlk[0, o] = sT[col] + 1.0
                blk_store(TR_OFF)
            _tick(3)                    # record word 3: start tick

        # ------------------------------------------------ prefetch phase
        # (static scheduler only: the dynamic scheduler cannot know a
        # slot's next task at compile time, so every dynamic task
        # demand-loads through its own record, words 28-30.)
        # Issue the NEXT task in this worker's stream into the B side of
        # this worker's double buffer.  The compiler emitted (off, ld,
        # rows) at words 24-26 only when the tile does not overlap
        # anything any worker writes in this or the next step, so reading
        # before those stores land is safe (that is the hazard analysis).
        def _primary_copy(off, ld, i, side):
            return pltpu.make_async_copy(
                hrow(off + i * ld, TN),
                sP.at[w_id, side, i, :, pl.ds(0, TN)],
                psem.at[w_id, side])

        if not DYN:
            pf_rows = d(26)

            @pl.when(pf_rows > 0)
            def _():
                _count(pf_rows)
                cadd(2, 1.0)

            def pf_body(i, _):
                @pl.when(i < pf_rows)
                def _():
                    _primary_copy(d(24), d(25), i, nslot).start()
                return 0
            jax.lax.fori_loop(0, TM, pf_body, 0)

        def _gate(pred):
            """Conjoin ``pred`` with "this slot popped a task" under the
            dynamic scheduler (idle pad slots must not execute)."""
            return pred if not DYN else jnp.logical_and(popped, pred)

        # ------------------------------------------- event wait (word 32)
        # Cross-worker producers synchronize through the in-heap event
        # table.  The in-order grid already satisfies every dependency
        # (static: proved by the compiler; dynamic: a task is only ever
        # popped after its event fully triggered), so the hardware
        # spin-wait degrades to a checked assertion: the counter must
        # ALREADY equal the trigger count; anything else is a
        # compiler/scheduler bug, counted as a violation.
        @pl.when(_gate(d(32) >= 0))
        def _():
            seen = peek(EVENT_OFF + d(32))
            cadd(5, 1.0)

            @pl.when(seen != d(33).astype(jnp.float32))
            def _():
                cadd(6, 1.0)

        # ------------------------------------------------- compute phase
        def primary():
            """This task's primary operand tile as a (TM, TN) value:
            either the A side filled by the previous step's prefetch
            (wait on the per-worker slot semaphore), or a demand bulk
            load when no prefetch was possible.  Rows >= sp_rows are
            zeroed."""
            rows = d(30)

            @pl.when(d(27) == 1)
            def _():                     # prefetched at step s-1
                def wbody(i, _):
                    @pl.when(i < rows)
                    def _():
                        _primary_copy(d(28), d(29), i, slot).wait()
                    return 0
                jax.lax.fori_loop(0, TM, wbody, 0)

            @pl.when(jnp.logical_and(d(27) == 0, rows > 0))
            def _():                     # hazard or first task: demand load
                _count(rows)
                cadd(3, 1.0)

                def sbody(i, _):
                    @pl.when(i < rows)
                    def _():
                        _primary_copy(d(28), d(29), i, slot).start()
                    return 0
                jax.lax.fori_loop(0, TM, sbody, 0)

                def fbody(i, _):
                    @pl.when(i < rows)
                    def _():
                        _primary_copy(d(28), d(29), i, slot).wait()
                    return 0
                jax.lax.fori_loop(0, TM, fbody, 0)

            def zbody(i, _):
                @pl.when(i >= rows)
                def _():
                    sP[w_id, slot, i] = jnp.zeros((1, TN), jnp.float32)
                return 0
            jax.lax.fori_loop(0, TM, zbody, 0)
            return sP[w_id, slot].reshape(TM, TN)

        cols = jax.lax.broadcasted_iota(jnp.int32, (1, TN), 1)

        # --------------- COMM span engine (kinds 14/15, multichip TP) ---
        # A per-row column window (m rows, independent src/dst row
        # strides — the chunk partition covers the REAL row width, so ld
        # pad columns never ride the ring) moves in COMM_BLOCK-word
        # masked blocks through the sR scratch: load the source block
        # and the current destination block, combine, write back with
        # words past the window restored from the loaded destination
        # (reads may run past the window into the neighbouring columns
        # or the stamper's trailing pad; writes never do).  In-order
        # execution makes the read-modify-write exact — on real hardware
        # each chip only ever combines into its OWN output windows and
        # staging buffers, so the blocks race with nothing.
        def span_op(src0, s_ld, dst0, d_ld, m, nwords, combine):
            nblk = (nwords + COMM_BLOCK - 1) // COMM_BLOCK

            @pl.when(nwords > 0)
            def _():
                _count(3 * nblk * m)    # src load + dst load + writeback

            lane0 = jax.lax.broadcasted_iota(jnp.int32, (1, COMM_BLOCK), 1)

            def row_body(r, _):
                sbase = src0 + r * s_ld
                dbase = dst0 + r * d_ld

                def body(i, _):
                    base = i * COMM_BLOCK
                    _copy(heap.at[:, pl.ds(sbase + base, COMM_BLOCK)],
                          sR.at[0])
                    _copy(heap.at[:, pl.ds(dbase + base, COMM_BLOCK)],
                          sR.at[1])
                    rel = lane0 + base      # window-relative col index
                    new = combine(sR[0], sR[1], rel)
                    sR[1] = jnp.where(rel < nwords, new, sR[1])
                    _copy(sR.at[1],
                          heap.at[:, pl.ds(dbase + base, COMM_BLOCK)])
                    return 0
                jax.lax.fori_loop(0, nblk, body, 0)
                return 0
            jax.lax.fori_loop(0, m, row_body, 0)

        def k_remote_copy():
            """COMM neighbour send: copy this chip's chunk into the peer
            chip's staging buffer; the arrival event signal rides the
            standard word-34 path after the copy lands (the in-heap
            event table mirrors the cross-chip counters)."""
            if RDMA:
                # real transport: the same per-row windows stream to the
                # peer chip (word 21) through the chip-to-chip
                # interconnect; the send/recv DMA semaphore pair tracks
                # wire completion, the arrival counter still rides
                # word 34.
                nblk = (d(3) + COMM_BLOCK - 1) // COMM_BLOCK

                def row_body(r, _):
                    def body(i, _):
                        base = i * COMM_BLOCK
                        cp = pltpu.make_async_remote_copy(
                            src_ref=heap.at[:, pl.ds(
                                d(6) + r * d(7) + base, COMM_BLOCK)],
                            dst_ref=heap.at[:, pl.ds(
                                d(4) + r * d(5) + base, COMM_BLOCK)],
                            send_sem=rsend, recv_sem=rrecv,
                            device_id=d(21),
                            device_id_type=pltpu.DeviceIdType.LOGICAL)
                        cp.start()
                        cp.wait()
                        return 0
                    jax.lax.fori_loop(0, nblk, body, 0)
                    return 0
                jax.lax.fori_loop(0, d(1), row_body, 0)
            else:
                span_op(d(6), d(7), d(4), d(5), d(1), d(3),
                        lambda s, dv, rel: s)

        def k_ar_chunk():
            """COMM arrival/init: owner-masked init (mode 0, keep only
            the owned columns of the replicated input — what makes the
            ring reduction bitwise-exact), accumulate a staged chunk
            (mode 1, reduce-scatter arrival) or store it (mode 2,
            all-gather arrival)."""
            def combine(s, dv, rel):
                owned = jnp.logical_and(rel >= d(15),
                                        rel < d(15) + d(16))
                init = jnp.where(owned, s, 0.0)
                return jnp.where(d(14) == 0, init,
                                 jnp.where(d(14) == 1, dv + s, s))
            span_op(d(6), d(7), d(4), d(5), d(1), d(3), combine)

        # ------------------------------------------------------------ kinds
        def k_noop():
            pass

        def k_matmul():
            m, n, k = d(1), d(2), d(3)
            pa = primary()

            def chunk(k0, xa):
                load_tile(sB, d(8) + k0 * d(9), d(9),
                          jnp.clip(k - k0, 0, TKC), SB_ROWS, TN)
                acc[...] += _dot(xa, mat(sB, TKC, TN), ((1,), (0,)))

            acc[...] = jnp.zeros((TM, TN), jnp.float32)
            chunk(0, pa[:, :TKC])

            def kbody(kc, _):
                k0 = kc * TKC
                load_tile(sA, d(6) + k0, d(7), m, TM, TKC)
                chunk(k0, mat(sA, TM, TKC))
                return 0
            jax.lax.fori_loop(1, KCH, kbody, 0)
            y = acc[...]
            @pl.when(d(10) >= 0)
            def _():
                load_row(sC, 0, d(10), TN)
            @pl.when(d(10) < 0)
            def _():
                sC[0, :, :TN] = jnp.zeros((1, TN), jnp.float32)
            y = y + sC[0, :, :TN]
            store_out(_act(y, d(14)), m, n)

        def k_rmsnorm():
            m, n = d(1), d(2)
            pa = primary()
            load_row(sC, 0, d(10), TN)
            x = pa[:, :TN]
            mean = jnp.sum(x * x, axis=1, keepdims=True) / n.astype(jnp.float32)
            inv = jax.lax.rsqrt(mean + _f32(d(17)))
            w = sC[0, :, :TN]
            wg = jnp.where(d(14) == 1, 1.0 + w, w)
            y = x * inv * wg
            # keep pad columns zero (gemma's 1+w would leak 1·0=0 anyway)
            y = jnp.where(cols < n, y, 0.0)
            store_out(y, m, n)

        def k_rope():
            # rotate-half per head, evaluated across the whole row: lane
            # j of head h pairs with lane j ± HD/2 of the same head
            m, n = d(1), d(2)
            pa = primary()
            half = HD // 2
            hl = jax.lax.rem(cols, HD)           # lane within its head
            fi = jax.lax.rem(hl, half)           # frequency index
            inv_freq = THETA ** (-fi.astype(jnp.float32) / half)
            first = hl < half
            heads = cols < (TN // HD) * HD
            _count_tile(m)                       # the positions gather
            for r in range(TM):
                @pl.when(r < m)
                def _(r=r):
                    if MROPE:
                        p = [peek(d(19) + r * d(20) + si)
                             for si in range(len(MROPE))]
                        # section si covers frequencies [start_si, end_si)
                        pos = jnp.full((1, TN), p[-1], jnp.float32)
                        end = sum(MROPE[:-1])
                        for si in range(len(MROPE) - 2, -1, -1):
                            pos = jnp.where(fi < end, p[si], pos)
                            end -= MROPE[si]
                    else:
                        pos = peek(d(19) + r * d(20))
                    ang = pos * inv_freq
                    cosv, sinv = jnp.cos(ang), jnp.sin(ang)
                    y = pa[r:r + 1, :TN]
                    nxt = pltpu.roll(y, TN - half, 1)    # y[j + half]
                    prv = pltpu.roll(y, half, 1)         # y[j - half]
                    rot = jnp.where(first, y * cosv - nxt * sinv,
                                    y * cosv + prv * sinv)
                    sO[r] = jnp.where(heads, rot, 0.0)
            store_tile(sO, d(4), d(5), m, TM, TN, valid=n)

        def k_glu():
            m = d(1)
            pa = primary()
            load_tile(sD, d(8), d(9), m, TM, TN)
            store_out(_act(pa[:, :TN], d(14)) * mat(sD, TM, TN), m, d(2))

        def k_resid():
            m = d(1)
            pa = primary()
            y = pa[:, :TN] * _f32(d(17))
            @pl.when(d(8) >= 0)
            def _():
                load_tile(sD, d(8), d(9), m, TM, TN)
            @pl.when(d(8) < 0)
            def _():
                sD[:TM] = jnp.zeros((TM, 1, TN), jnp.float32)
            store_out(y + mat(sD, TM, TN), m, d(2))

        def k_attn():
            m, n = d(1), d(2)
            scale = _f32(d(17))
            primary()                                      # q tile
            _count(1)                                      # live lens row
            lo = blk_load(d(12))
            srow = jax.lax.broadcasted_iota(jnp.int32, (TS, 1), 0)

            def row_body(r, _):
                live = sBlk[0, lo + r].astype(jnp.int32)
                sO[r] = jnp.zeros((1, TN), jnp.float32)

                def group_body(gi, _):
                    q0 = pl.multiple_of(gi * (G * HD), G * HD)
                    qg = sP[w_id, slot, r, :, pl.ds(q0, G * HD)]
                    qm = qg.reshape(G, HD) * scale

                    def chunk_body(sc, carry):
                        mrun, lrun, arun = carry
                        s0 = sc * TS
                        valid = jnp.clip(live - s0, 0, TS)
                        load_tile(sB, d(8) + r * d(15) + gi * HD
                                  + s0 * d(9), d(9), valid, TS, HD)
                        load_tile(sD, d(10) + r * d(15) + gi * HD
                                  + s0 * d(11), d(11), valid, TS, HD)
                        logits = _dot(mat(sB, TS, HD), qm,
                                      ((1,), (1,)))             # (TS,G)
                        logits = jnp.where(srow < valid, logits, -1e30)
                        mnew = jnp.maximum(
                            mrun, jnp.max(logits, axis=0, keepdims=True))
                        p = jnp.exp(logits - mnew)
                        corr = jnp.exp(mrun - mnew)
                        lrun = lrun * corr + jnp.sum(p, axis=0,
                                                     keepdims=True)
                        arun = (arun * corr.reshape(G, 1)
                                + _dot(p, mat(sD, TS, HD), ((0,), (0,))))
                        return mnew, lrun, arun

                    mrun, lrun, arun = jax.lax.fori_loop(
                        0, SCH, chunk_body,
                        (jnp.full((1, G), -1e30, jnp.float32),
                         jnp.zeros((1, G), jnp.float32),
                         jnp.zeros((G, HD), jnp.float32)))
                    og = arun / jnp.maximum(lrun, 1e-30).reshape(G, 1)
                    sO[r, :, pl.ds(q0, G * HD)] = og.reshape(1, G * HD)
                    return 0
                jax.lax.fori_loop(0, NG, group_body, 0)
                store_row_vec(sO, r, d(4) + r * d(5), TN, valid=n)
                return 0
            jax.lax.fori_loop(0, m, row_body, 0)

        def k_cache_update():
            m = d(1)
            primary()                                      # new K/V rows
            _count(1)                                      # seq lens row
            lo = blk_load(d(12))

            def row_body(r, _):
                seq = sBlk[0, lo + r].astype(jnp.int32)
                store_primary_row(r, d(4) + r * d(15) + seq * d(5),
                                  valid=d(2))
                return 0
            jax.lax.fori_loop(0, m, row_body, 0)

        def k_embed():
            m = d(1)
            primary()                                      # token ids (f32)
            lo = blk_load(d(6))

            def row_body(r, _):
                tok = sBlk[0, lo + r].astype(jnp.int32)
                load_row(sA, r, d(8) + tok * d(9), TN)
                store_row_vec(sA, r, d(4) + r * d(5), TN, valid=d(2))
                return 0
            jax.lax.fori_loop(0, m, row_body, 0)

        def k_softmax_topk():
            m, n = d(1), d(2)
            pa = primary()
            masked = jnp.where(cols < n, pa[:, :TN], -jnp.inf)
            sel = jnp.zeros((TM, TN, TOPK), jnp.float32)
            vals = jnp.zeros((TM, TOPK), jnp.float32)
            for i in range(TOPK):
                cmax = jnp.max(masked, axis=1, keepdims=True)
                hit = (masked == cmax)
                first = hit & (jnp.cumsum(hit.astype(jnp.int32), axis=1) == 1)
                vals = vals.at[:, i].set(cmax[:, 0])
                sel = sel.at[:, :, i].set(first.astype(jnp.float32))
                masked = jnp.where(first, -jnp.inf, masked)
            w = jax.nn.softmax(vals, axis=1)                  # (TM, K)
            store_out(jnp.einsum("mek,mk->me", sel, w), m, n)

        def k_moe_gg():
            m, n, k = d(1), d(2), d(3)
            pa = primary()
            # router column for this expert -> per-token mask
            load_col(1, d(10), d(11), m, TM)
            mask = (sC[1, 0, :TM] > 0).astype(jnp.float32)[:, None]
            acc[...] = jnp.zeros((TM, TN), jnp.float32)
            acc2[...] = jnp.zeros((TM, TN), jnp.float32)
            for kc in range(KCH):
                k0 = kc * TKC
                if kc == 0:
                    xa = pa[:, :TKC] * mask
                else:
                    load_tile(sA, d(6) + k0, d(7), m, TM, TKC)
                    xa = mat(sA, TM, TKC) * mask
                load_tile(sB, d(8) + k0 * d(9), d(9),
                          jnp.clip(k - k0, 0, TKC), SB_ROWS, TN)
                acc[...] += _dot(xa, mat(sB, TKC, TN), ((1,), (0,)))
                @pl.when(d(15) == 1)
                def _():
                    load_tile(sB, d(19) + k0 * d(9), d(9),
                              jnp.clip(k - k0, 0, TKC), SB_ROWS, TN)
                    acc2[...] += _dot(xa, mat(sB, TKC, TN), ((1,), (0,)))
            y = jnp.where(d(15) == 1,
                          _act(acc[...], d(14)) * acc2[...],
                          acc[...])
            store_out(y, m, n)

        def k_moe_combine():
            m, n, n_exp = d(1), d(2), d(3)
            acc[...] = jnp.zeros((TM, TN), jnp.float32)
            for e in range(EMAX):
                live = (e < n_exp)
                load_tile(sD, d(6) + e * d(15), d(7),
                          jnp.where(live, m, 0), TM, TN)
                load_col(1, d(10) + e, d(11),
                         jnp.where(live, m, 0), TM)
                acc[...] += mat(sD, TM, TN) * sC[1, 0, :TM][:, None]
            store_out(acc[...], m, n)

        def k_ssm():
            # the x tile's heads [0, NHT) hold HDS lanes each; the state
            # update needs each head's x as a column, so the tile is
            # transposed once and each (row, head) column is picked out
            m = d(1)
            pa = primary()                                 # x tile
            xT = pa[:, :NHT * HDS].T                       # (NHT*HDS, TM)
            hcol = jax.lax.broadcasted_iota(jnp.int32, (HDS, TM), 1)
            _count(1)                                      # A_log row
            @pl.when(d(23) >= 0)
            def _():
                _count(1)                                  # D skip row
            has_d = d(23) >= 0
            heads = []
            for hh in range(NHT):                          # per-head scalars
                a_log = jnp.full((1, 1), peek(d(12) + hh), jnp.float32)
                dsk = jnp.where(has_d, peek(jnp.maximum(d(23), 0) + hh),
                                0.0)
                heads.append((a_log, jnp.full((1, 1), dsk, jnp.float32)))
            for r in range(TM):
                @pl.when(r < m)
                def _(r=r):
                    _count(1)                              # dt (head slice)
                    load_row(sC, 0, d(19) + r * d(20), TN)
                    bvec = sC[0, :, :NS]
                    load_row(sC, 1, d(21) + r * d(22), TN)
                    cvec = sC[1, :, :NS]
                    for hh in range(NHT):
                        a_log, dsk = heads[hh]
                        base = d(8) + r * d(15) + hh * d(16)
                        load_tile(sB, base, d(9), HDS, SB_ROWS, NS)
                        x_h = jnp.sum(jnp.where(
                            hcol == r, xT[hh * HDS:(hh + 1) * HDS], 0.0),
                            axis=1, keepdims=True)          # (HDS, 1)
                        dt_sp = jax.nn.softplus(jnp.full(
                            (1, 1), peek(d(10) + r * d(11) + hh),
                            jnp.float32))
                        da = jnp.exp(dt_sp * (-jnp.exp(a_log)))
                        new_state = (mat(sB, HDS, NS) * da
                                     + (dt_sp * x_h) * bvec)
                        y_h = (_dot(new_state, cvec, ((1,), (1,)))
                               + dsk * x_h)                 # (HDS, 1)
                        sB[:HDS, :, :NS] = new_state.reshape(HDS, 1, NS)
                        store_tile(sB, base, d(9), HDS, SB_ROWS, NS)
                        sY[hh * HDS:(hh + 1) * HDS, r:r + 1] = y_h
            y = sY[...].T                                   # (TM, NHT*HDS)
            sO[...] = jnp.zeros((TM, 1, TN), jnp.float32)
            sO[:, :, :NHT * HDS] = y.reshape(TM, 1, NHT * HDS)
            for r in range(TM):
                @pl.when(r < m)
                def _(r=r):
                    store_row_vec(sO, r, d(4) + r * d(5), TN, valid=d(2))

        def k_conv():
            m = d(1)
            pa = primary()                                 # x tile
            load_tile(sB, d(10), d(11), WC, SB_ROWS, TN)   # conv_w (W, n)
            @pl.when(d(12) >= 0)
            def _():
                load_row(sC, 0, d(12), TN)
            @pl.when(d(12) < 0)
            def _():
                sC[0, :, :TN] = jnp.zeros((1, TN), jnp.float32)
            bias = sC[0, :, :TN]
            for r in range(TM):
                @pl.when(r < m)
                def _(r=r):
                    base = d(8) + r * d(15)
                    load_tile(sD, base, d(9), WC, WC, TN)
                    rows = ([sD[j, :, :TN] for j in range(1, WC)]
                            + [pa[r:r + 1, :TN]])
                    y = bias
                    for j in range(WC):
                        sD[j, :, :TN] = rows[j]
                        y = y + rows[j] * sB[j, :, :TN]
                    store_tile(sD, base, d(9), WC, WC, TN, valid=d(2))
                    sO[r] = jax.nn.silu(y)
                    store_row_vec(sO, r, d(4) + r * d(5), TN,
                                  valid=d(2))

        branches = [k_noop, k_matmul, k_rmsnorm, k_rope, k_glu, k_resid,
                    k_attn, k_cache_update, k_embed, k_softmax_topk,
                    k_moe_gg, k_moe_combine, k_ssm, k_conv, k_remote_copy,
                    k_ar_chunk]

        present = sorted(kinds)

        def _dispatch():
            # switch over the kinds this plan holds only: Mosaic lowers a
            # switch as nested ifs, and its compiler crashes on a task
            # branch nested 12 or more levels deep
            idx = jnp.int32(0)
            for i, code in enumerate(present):
                idx = jnp.where(d(0) == code, jnp.int32(i), idx)
            jax.lax.switch(idx, [branches[code] for code in present])

        if DYN:
            @pl.when(popped)
            def _():
                _dispatch()
        else:
            _dispatch()

        # --------------------------------- enqueue one newly-ready task
        def _push(crow):
            """Push descriptor row ``crow`` into its affinity worker's
            pool (word 35; first empty slot), spilling to the shared
            overflow queue when the pool is full."""
            aw = desc[crow, 35]
            _qrow(0, QOFF + aw * QCAP)
            free = sQ[0, 0, :] >= _QTH
            row_id = crow.astype(jnp.float32)

            @pl.when(jnp.any(free))
            def _():
                fidx = jnp.argmax(free).astype(jnp.int32)
                poke(QOFF + aw * QCAP + fidx, row_id)
                poke_add(QC_OFF + 2 * aw, 1.0)       # pushed cursor

            @pl.when(jnp.logical_not(jnp.any(free)))
            def _():                     # affinity pool full: overflow
                for r in range(OV_ROWS):
                    _qrow(r, OVOFF + r * QCAP)
                ofree = (sQ[:OV_ROWS, 0, :] >= _QTH).reshape(-1)
                oidx = jnp.argmax(ofree).astype(jnp.int32)
                poke(OVOFF + oidx, row_id)
                poke_add(QC_OFF + 2 * W, 1.0)

        # ------------------------- event signal-and-enqueue (word 34)
        # After this task's stores have landed, increment its triggering
        # event's in-heap counter (a read-modify-write through SMEM; on
        # real parallel hardware this is the atomic the event table
        # provides — the in-order grid makes it exact).  Under the
        # dynamic scheduler, the producer whose increment brings the
        # counter to the trigger count walks the event's consumer list
        # and enqueues every newly-ready task.
        @pl.when(_gate(d(34) >= 0))
        def _():
            count = poke_add(EVENT_OFF + d(34), 1.0)
            cadd(7, 1.0)
            if DYN and MAX_OUT > 0:
                trig = sched[d(34), 0].astype(jnp.float32)

                @pl.when(count == trig)
                def _():                 # event fully triggered
                    def push_body(j, _):
                        @pl.when(j < sched[d(34), 1])
                        def _():
                            _push(sched[d(34), 2 + j])
                        return 0
                    jax.lax.fori_loop(0, MAX_OUT, push_body, 0)

        def blk_write(addr, words):
            """heap[addr + j] = words[j] through the SMEM block."""
            o = blk_load(addr)
            for j, v in enumerate(words):
                sBlk[0, o + j] = v
            blk_store(addr)

        # ------------------- trace ring: assemble + store the record ---
        # Every grid slot writes its full TRACE_WORDS record (static
        # noops record kind 0; dynamic idle slots record row/kind -1) so
        # no stale data from a previous launch survives in the ring.
        if TRACE:
            _tick(4)                    # record word 4: end tick
            sT[0] = w_id.astype(jnp.float32)
            if DYN:
                sT[1] = jnp.where(popped, t.astype(jnp.float32), -1.0)
                sT[2] = jnp.where(popped, d(0).astype(jnp.float32), -1.0)
                sT[5] = sS[0].astype(jnp.float32)
            else:
                sT[1] = t.astype(jnp.float32)
                sT[2] = d(0).astype(jnp.float32)
                sT[5] = jnp.float32(-1.0)
            sT[6] = jnp.where(_gate(d(32) >= 0),
                              d(33).astype(jnp.float32), 0.0)
            sT[7] = jnp.float32(0.0)
            blk_write(TR_OFF + TRACE_HEADER
                      + (s * W + w_id) * TRACE_WORDS,
                      [sT[j] for j in range(TRACE_WORDS)])

        # flush the per-worker counter blocks to their reserved heap
        # slots — only the final grid iteration: the totals accumulate in
        # scratch and nothing reads the heap copy mid-launch
        @pl.when(jnp.logical_and(s == num_steps - 1, w_id == W - 1))
        def _():
            for ww in range(W):
                blk_write(STATS_OFF + ww * STATS_WORDS,
                          [cnt[ww, j] for j in range(STATS_WORDS)])

    sd_rows = max(TM, TS, WC, 8)
    scratch_shapes = [
        pltpu.VMEM((TM, 1, TNK), jnp.float32),        # sA
        pltpu.VMEM((SB_ROWS, 1, TN), jnp.float32),    # sB
        pltpu.VMEM((max(8, TM), 1, max(TN, TM)), jnp.float32),  # sC
        pltpu.VMEM((sd_rows, 1, TN), jnp.float32),    # sD
        pltpu.VMEM((TM, TN), jnp.float32),            # acc (matmul sums)
        pltpu.VMEM((TM, TN), jnp.float32),            # acc2
        pltpu.VMEM((TM, 1, TN), jnp.float32),         # sO (output rows)
        pltpu.VMEM((NHT * HDS, TM), jnp.float32),     # sY (SSM out cols)
        pltpu.VMEM((W, 2, TM, 1, TN), jnp.float32),   # sP (per-worker
                                                      #     double buffer)
        pltpu.SMEM((1, _BLK), jnp.float32),           # sBlk (scalar words)
        pltpu.SMEM((W, STATS_WORDS), jnp.float32),    # cnt (per-worker)
        pltpu.VMEM((2, 1, COMM_BLOCK), jnp.float32),  # sR (comm span blocks)
        pltpu.SemaphoreType.DMA,                      # sem (bulk tiles)
        pltpu.SemaphoreType.DMA((W, 2)),              # psem (worker, slot)
        pltpu.SemaphoreType.DMA,                      # rsend (remote DMA)
        pltpu.SemaphoreType.DMA,                      # rrecv (remote DMA)
    ]
    if DYN:
        scratch_shapes += [
            pltpu.VMEM((OV_ROWS, 1, QCAP), jnp.float32),  # sQ (pool scans)
            pltpu.SMEM((8,), jnp.int32),                  # sS (pop state)
        ]
    if TRACE:
        scratch_shapes += [
            pltpu.SMEM((TRACE_WORDS,), jnp.float32),      # sT (trace rec)
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if DYN else 1,
        grid=(num_steps, W),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch_shapes,
    )
    return functools.partial(
        pl.pallas_call,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, HEAP), jnp.float32),
        input_output_aliases={2 if DYN else 1: 0},
        interpret=interpret,
        name="mpk_megakernel",
    )(kernel)
