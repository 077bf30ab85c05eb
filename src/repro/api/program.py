"""``mpk.Program``: one compile-once / step-many API over all three
execution backends.

The paper's user-facing unit is a *compiled executable*: compile the
decode step once, then run every decode step inside it while KV-cache and
SSM state stay resident.  ``compile()`` returns a :class:`Program` — a
stateful compiled executable with a uniform contract:

    prog = mpk.compile(cfg, batch=4, max_seq=128, backend="megakernel")
    prog.bind(params)              # weights packed/uploaded exactly once
    prog.init_state()              # zero KV/conv/SSM state in place
    logits = prog.step(tokens, seq_lens)        # one decode step
    logits = prog.prefill(chunk, seq_lens, chunk_lens)  # N-token chunks

Backends (interchangeable, logits parity-tested against each other):

* ``"jax"``         — the model oracle (``prefill_chunk`` / ``serve_step``)
* ``"interpreter"`` — the numpy tGraph interpreter (compiler semantics)
* ``"megakernel"``  — the persistent Pallas kernel: ONE ``make_megakernel``
  + jit trace per program, ONE full weight upload at ``bind()``, state
  carried in the device-resident heap via buffer donation/aliasing, and
  per-step inputs written through a small partial heap update.

``prefill`` always executes through the JAX chunked-prefill path against
the program's state (the megakernel covers decode — the paper's
persistent-kernel workload); for non-JAX backends the state round-trips
through ``get_state``/``set_state``, so a serving engine can mix chunked
prefill and in-kernel decode on one Program.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.compile import CompiledTGraph, CompileOptions, megakernelize
from ..core.decompose import DecomposeConfig
from ..core.interpreter import execute_tgraph
from ..core.lowering import build_decode_graph, decode_bindings
from ..models import init_cache, prefill_chunk
from ..models.lm import block_structure

__all__ = ["BACKENDS", "Program", "compile"]

BACKENDS = ("jax", "interpreter", "megakernel")


def _jsonable(obj):
    """Recursively convert a stats structure to plain JSON types (numpy
    scalars/arrays included); anything exotic degrades to ``str``."""
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, np.generic):
        return obj.item()
    if isinstance(obj, (bool, int, float, str)) or obj is None:
        return obj
    return str(obj)


# ---------------------------------------------------------------------------
# State map: graph state tensors <-> the stacked cache pytree.
# ---------------------------------------------------------------------------


def _state_map(cfg) -> List[Dict[str, Any]]:
    """One entry per graph state tensor: its input/output names and where
    it lives in the ``init_cache`` pytree (leaf key + (block, index))."""
    st = block_structure(cfg)
    period = st["period"]
    out: List[Dict[str, Any]] = []
    for i in range(cfg.n_layers):
        L = f"L{i}"
        blk, pos = divmod(i, period)
        if cfg.layer_kind(i) == "attn":
            ai = st["attn_pos"].index(pos)
            for name, key in ((f"{L}.k_cache", "k"), (f"{L}.v_cache", "v")):
                out.append({"in": name, "out": name + "2", "key": key,
                            "blk": blk, "idx": ai})
        else:
            si = st["ssm_pos"].index(pos)
            for tag in ("x", "b", "c"):
                out.append({"in": f"{L}.conv_{tag}_state",
                            "out": f"{L}.conv_{tag}_state2",
                            "key": f"conv_{tag}", "blk": blk, "idx": si})
            out.append({"in": f"{L}.ssm_state", "out": f"{L}.ssm_state2",
                        "key": "ssm", "blk": blk, "idx": si})
    return out


def _np_tree(tree):
    # np.array (not asarray): jnp arrays view as read-only buffers, and
    # the interpreter/heap paths write state in place
    return jax.tree.map(lambda a: np.array(a, np.float32), tree)


# ---------------------------------------------------------------------------
# The Program contract.
# ---------------------------------------------------------------------------


class Program:
    """A compiled, stateful decode executable (compile once / step many).

    Subclasses implement ``step`` (one decode step through the backend)
    and ``get_state``/``set_state``; ``prefill`` and ``reset_slot`` are
    shared.  ``step`` and ``prefill`` return numpy logits; ``get_state``
    returns the backend's own arrays (device arrays on the jax and
    megakernel backends).
    """

    backend = "abstract"

    def __init__(self, cfg, batch: int, max_seq: int,
                 step_cache: Optional[Dict[tuple, Callable]] = None,
                 pipeline_depth: int = 2, num_workers: int = 1):
        self.cfg = cfg
        self.batch = batch
        self.max_seq = max_seq
        self.pipeline_depth = pipeline_depth
        self.num_workers = num_workers
        self.step_count = 0
        # (cfg, width)-keyed jitted prefill fns; pass a shared dict to
        # reuse compiled steps across programs/engines (benchmark warmup)
        self._steps: Dict[tuple, Callable] = \
            step_cache if step_cache is not None else {}
        self._params: Any = None
        self._params_dev: Any = None   # jnp mirror for the prefill path
        self._compiled: Optional[CompiledTGraph] = None

    # ----------------------------------------------------------- lifecycle
    def bind(self, params) -> "Program":
        """Attach (and for device backends, upload) the weights. Once."""
        raise NotImplementedError

    def init_state(self) -> "Program":
        """(Re)zero all KV/conv/SSM state; does not touch weights."""
        raise NotImplementedError

    def step(self, tokens_or_embeds, seq_lens, positions=None) -> np.ndarray:
        """One decode step for the whole batch; returns logits (B, vocab).
        State advances in place; the caller owns ``seq_lens``."""
        raise NotImplementedError

    # ------------------------------------------------------------- state
    def get_state(self) -> Dict[str, Any]:
        """The cache/state pytree (``init_cache`` layout).  On the
        megakernel backend a fresh snapshot, which later ``step``,
        ``prefill`` and ``set_state`` calls leave as it is."""
        raise NotImplementedError

    def set_state(self, state: Dict[str, Any]) -> None:
        raise NotImplementedError

    def reset_slot(self, slot: int) -> None:
        """Zero one batch row's state (serving: slot reuse on admission)."""
        raise NotImplementedError

    # ------------------------------------------------------------ prefill
    def _prefill_fn(self, n: int) -> Callable:
        """Jitted chunked-prefill step for width ``n``, keyed by (cfg, n)
        so a shared cache never hands one model's step to another."""
        key = (self.cfg, n)
        if key not in self._steps:
            cfg = self.cfg

            # the name is the jit's, so its device ops read ``mpk_prefill``
            # in a profile
            def mpk_prefill(params, cache, tokens, seq_lens, chunk_lens):
                return prefill_chunk(params, cfg, cache, tokens, seq_lens,
                                     chunk_lens)

            self._steps[key] = jax.jit(mpk_prefill, donate_argnums=(1,))
        return self._steps[key]

    def _dev_params(self):
        if self._params_dev is None:
            assert self._params is not None, "bind() before prefill()"
            self._params_dev = jax.tree.map(jnp.asarray, self._params)
        return self._params_dev

    def prefill(self, tokens_or_embeds, seq_lens,
                chunk_lens=None) -> np.ndarray:
        """Consume an N-token chunk per request; returns logits (B, N, V).
        Positions >= ``chunk_lens`` are padding (no state written).

        Prefill always runs through the JAX chunked path against this
        program's state; decode steps go through the backend.

        Spans (``obs.span``): ``prefill`` (attribute ``width``), and inside
        it ``prefill.gather`` (``get_state``), ``prefill.compute`` (the
        jitted chunk until its results are ready), ``prefill.scatter``
        (``set_state``) and ``prefill.readback`` (logits to the host)."""
        n = np.asarray(tokens_or_embeds).shape[1]
        b = self.batch
        if chunk_lens is None:
            chunk_lens = np.full((b,), n, np.int32)
        fn = self._prefill_fn(n)
        with obs.span("prefill", width=n):
            with obs.span("prefill.gather"):
                cache = jax.tree.map(jnp.asarray, self.get_state())
            with obs.span("prefill.compute"):
                logits, cache = jax.block_until_ready(fn(
                    self._dev_params(), cache, jnp.asarray(tokens_or_embeds),
                    jnp.asarray(np.asarray(seq_lens, np.int32)),
                    jnp.asarray(np.asarray(chunk_lens, np.int32))))
            with obs.span("prefill.scatter"):
                self.set_state(cache)
            with obs.span("prefill.readback"):
                return np.asarray(logits)

    # ------------------------------------------------------------- stats
    @property
    def compiled(self) -> CompiledTGraph:
        """The compiled tGraph (built lazily for the jax backend)."""
        if self._compiled is None:
            g = build_decode_graph(self.cfg, self.batch, self.max_seq)
            self._compiled = megakernelize(g, CompileOptions(
                pipeline_depth=self.pipeline_depth,
                num_workers=self.num_workers))
        return self._compiled

    @property
    def stats(self) -> Dict[str, Any]:
        return self.compiled.stats

    @property
    def pipeline_stats(self) -> Dict[str, Any]:
        """The schedule→kernel pipeline contract, compiler side: stall
        counts at the configured pipeline depth and the scheduler's
        reduction over naive linearization.  The megakernel backend
        extends this with the prefetch plan's coverage and — after a
        step — the kernel's own DMA counters."""
        s = self.compiled.stats
        return {
            "stalls": s.get("pipeline_stalls", 0),
            "stalls_naive": s.get("pipeline_stalls_naive",
                                  s.get("pipeline_stalls", 0)),
            "stall_reduction": s.get("stall_reduction", 1.0),
            "pipeline_depth": s.get("pipeline_depth", 2),
        }

    @property
    def worker_stats(self) -> Dict[str, Any]:
        """The W-worker schedule→runtime contract: the compiler's worker
        partition (queue lengths, cross-worker event cut) plus the
        simulator's replay of that exact partition (makespan, per-worker
        utilization).  The megakernel backend extends this with the
        kernel's own per-worker DMA/event counters after a step."""
        from ..core.runtime_sim import SimConfig, simulate
        part = self.compiled.partition
        res = simulate(self.compiled,
                       SimConfig(mode="mpk", n_workers=part.requested_workers,
                                 pipeline_depth=self.pipeline_depth))
        return {
            "num_workers": part.num_workers,
            "requested_workers": part.requested_workers,
            "queue_lens": [len(q) for q in part.queues],
            "cross_worker_deps": len(part.cross_deps),
            "partition_steps": part.num_steps,
            "sim_makespan_us": res.makespan * 1e6,
            "worker_utilization": list(res.worker_busy or []),
        }

    def describe(self) -> Dict[str, Any]:
        c = self.compiled
        return {
            "backend": self.backend,
            "arch": self.cfg.name,
            "batch": self.batch,
            "max_seq": self.max_seq,
            "ops": len(c.graph.ops),
            "tasks": c.tg.num_tasks(),
            "events": c.stats["events_post_fusion"],
            "workspace_elements": c.stats["workspace_elements"],
        }

    # -------------------------------------------------- observability
    def predicted_trace(self):
        """The compiler's *predicted* per-task timeline as an
        ``obs.TaskTrace`` (roofline seconds): ``replay_partition`` — the
        prediction ``obs.reconcile`` checks against an observed trace."""
        from ..obs import predicted_task_trace
        part = self.compiled.partition
        return predicted_task_trace(
            self.compiled,
            num_workers=(part.requested_workers if part is not None
                         else self.num_workers),
            pipeline_depth=self.pipeline_depth,
            tp=getattr(self, "tp", 1))

    def trace(self):
        """The program's per-task timeline as an ``obs.TaskTrace``.

        Backend semantics: the megakernel returns the kernel-written
        trace ring (compile with ``trace=True``, run at least one step);
        the interpreter returns its sequential execution on the same
        two-ticks-per-task clock; the jax oracle executes whole
        operators (no per-task timeline exists), so it returns the
        predicted timeline."""
        return self.predicted_trace()

    def metrics_snapshot(self, serving: Optional[Dict[str, Any]] = None
                         ) -> Dict[str, Any]:
        """One JSON-ready dict joining every metrics surface: program
        identity (``describe``), compiler stats, the schedule→kernel
        pipeline contract, the worker/COMM counters, and —
        when a serving engine passes its ``metrics_summary()`` — the
        TTFT/TPOT/queue latency percentiles."""
        snap: Dict[str, Any] = {
            "program": self.describe(),
            "compiler": dict(self.stats),
            "pipeline": self.pipeline_stats,
            "workers": self.worker_stats,
            "step_count": self.step_count,
        }
        if serving is not None:
            snap["serving"] = dict(serving)
        return _jsonable(snap)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (f"Program<{self.backend}>({self.cfg.name}, "
                f"batch={self.batch}, max_seq={self.max_seq})")


# ---------------------------------------------------------------------------
# Backend: "jax" — the model oracle.
# ---------------------------------------------------------------------------


class JaxProgram(Program):
    backend = "jax"

    def __init__(self, cfg, batch, max_seq, step_cache=None,
                 pipeline_depth: int = 2, num_workers: int = 1):
        super().__init__(cfg, batch, max_seq, step_cache, pipeline_depth,
                         num_workers)
        self._cache = None
        # donated slot zeroing: no full-cache copy per admission
        self._jreset = jax.jit(
            lambda cache, slot: jax.tree.map(
                lambda a: a.at[:, :, slot].set(0), cache),
            donate_argnums=(0,))

    def bind(self, params) -> "Program":
        self._params = jax.tree.map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        self._params_dev = self._params
        return self

    def init_state(self) -> "Program":
        self._cache = init_cache(self.cfg, self.batch, self.max_seq,
                                 dtype=jnp.float32)
        return self

    def get_state(self):
        assert self._cache is not None, "init_state() first"
        return self._cache

    def set_state(self, state) -> None:
        # a copy: ``step`` donates the cache, and ``state`` may be a
        # snapshot its caller sets again
        self._cache = jax.tree.map(lambda a: jnp.array(a, copy=True), state)

    def step(self, tokens_or_embeds, seq_lens, positions=None) -> np.ndarray:
        # a decode step IS a width-1 chunk (models.serve_step contract)
        if self.cfg.embed_input:
            chunk = np.asarray(tokens_or_embeds)[:, None, :]
        else:
            chunk = np.asarray(tokens_or_embeds)[:, None]
        fn = self._prefill_fn(1)
        logits, self._cache = fn(
            self._params, self._cache, jnp.asarray(chunk),
            jnp.asarray(np.asarray(seq_lens, np.int32)),
            jnp.ones((self.batch,), jnp.int32))
        self.step_count += 1
        return np.asarray(logits[:, 0])

    def reset_slot(self, slot: int) -> None:
        self._cache = self._jreset(self._cache, jnp.int32(slot))


# ---------------------------------------------------------------------------
# Backend: "interpreter" — the numpy tGraph interpreter.
# ---------------------------------------------------------------------------


class InterpreterProgram(Program):
    backend = "interpreter"

    def __init__(self, cfg, batch, max_seq, step_cache=None, *,
                 options: Optional[CompileOptions] = None, tp: int = 1):
        super().__init__(cfg, batch, max_seq, step_cache,
                         options.pipeline_depth if options else 2,
                         options.num_workers if options else 1)
        g = build_decode_graph(cfg, batch, max_seq, tp=tp)
        t0 = time.perf_counter()
        self._compiled = megakernelize(g, options)
        # compiler wall time excludes graph build (table2 trend metric)
        self._compiled.stats["compile_wall_s"] = time.perf_counter() - t0
        self._smap = _state_map(cfg)
        self._cache = None

    def trace(self):
        """The interpreter's sequential execution as an ``obs.TaskTrace``
        on the kernel ring's two-ticks-per-task clock."""
        from ..obs import sequential_trace
        return sequential_trace(self._compiled)

    def bind(self, params) -> "Program":
        self._params = _np_tree(params)
        self._params_dev = None
        return self

    def init_state(self) -> "Program":
        self._cache = _np_tree(init_cache(self.cfg, self.batch,
                                          self.max_seq, dtype=jnp.float32))
        return self

    def get_state(self):
        assert self._cache is not None, "init_state() first"
        return self._cache

    def set_state(self, state) -> None:
        self._cache = _np_tree(state)

    def reset_slot(self, slot: int) -> None:
        for leaf in self._cache.values():  # in place: leaves are ours
            leaf[:, :, slot] = 0.0

    def step(self, tokens_or_embeds, seq_lens, positions=None) -> np.ndarray:
        assert self._params is not None, "bind() first"
        binds = decode_bindings(self.cfg, self._params, self._cache,
                                tokens_or_embeds, seq_lens, positions)
        out = execute_tgraph(self._compiled, binds)
        for ent in self._smap:  # fold updated state back into the pytree
            leaf = self._cache[ent["key"]]
            leaf[ent["blk"], ent["idx"]] = np.asarray(
                out[ent["out"]]).reshape(leaf.shape[2:])
        self.step_count += 1
        return np.asarray(out["logits"])


# ---------------------------------------------------------------------------
# Backend: "megakernel" — the persistent Pallas kernel.
# ---------------------------------------------------------------------------


class PallasProgram(Program):
    backend = "megakernel"

    def __init__(self, cfg, batch, max_seq, step_cache=None, *,
                 max_rows: int = 8, latency_aware: bool = True,
                 event_fusion: bool = True, pipeline_depth: int = 2,
                 num_workers: int = 1, tp: int = 1, trace: bool = False):
        super().__init__(cfg, batch, max_seq, step_cache, pipeline_depth,
                         num_workers)
        self.tp = tp
        # late import keeps the api package importable without pallas
        from ..kernels.megakernel import (MegakernelExecutor,
                                          compile_decode_megakernel)
        self.plan = compile_decode_megakernel(
            cfg, batch, max_seq, max_rows=max_rows,
            latency_aware=latency_aware, event_fusion=event_fusion,
            pipeline_depth=pipeline_depth, num_workers=num_workers,
            tp=tp, trace=trace)
        self._compiled = self.plan.compiled
        self.executor = MegakernelExecutor(self.plan, cfg)
        smap = _state_map(cfg)
        leaves = jax.eval_shape(lambda: init_cache(cfg, batch, max_seq,
                                                   dtype=jnp.float32))
        shapes = {e["in"]: self.plan.compiled.graph.spec(e["in"]).shape
                  for e in smap}

        # graph state tensors <-> the init_cache pytree, on the device
        def to_tree(tensors):
            tree = {k: jnp.zeros(s.shape, s.dtype) for k, s in leaves.items()}
            for e in smap:
                leaf = tree[e["key"]]
                tree[e["key"]] = leaf.at[e["blk"], e["idx"]].set(
                    tensors[e["in"]].reshape(leaf.shape[2:]))
            return tree

        def from_tree(state):
            return {e["in"]: state[e["key"]][e["blk"], e["idx"]].astype(
                        jnp.float32).reshape(shapes[e["in"]])
                    for e in smap}

        self._jto_tree = jax.jit(to_tree)
        self._jfrom_tree = jax.jit(from_tree)

    def trace(self):
        """The kernel-written trace ring of the LAST step as an
        ``obs.TaskTrace`` (logical ticks).  Requires ``trace=True`` at
        compile and at least one executed step."""
        from ..obs import decode_ring
        if not self.plan.trace:
            raise ValueError("program compiled without trace=True — "
                             "the kernel wrote no trace ring")
        if self.step_count == 0:
            raise ValueError("no step executed yet — the trace ring is "
                             "empty; run step() first")
        return decode_ring(self.plan, self.executor.task_ring())

    # the compile-once guarantees, surfaced for tests/benchmarks
    @property
    def trace_count(self) -> int:
        return self.executor.trace_count

    @property
    def upload_count(self) -> int:
        return self.executor.upload_count

    @property
    def pipeline_stats(self) -> Dict[str, Any]:
        """Compiler stats + the static prefetch plan (coverage over the
        descriptor table) + — once a step has run — the kernel's own
        per-step DMA counters (bulk tile DMAs vs the row copies they
        batch, prefetch hits, demand-load misses)."""
        out = dict(Program.pipeline_stats.fget(self))
        out.update(self.plan.pipeline_stats())
        if self.step_count > 0:
            out.update(self.executor.pipeline_counters())
        return out

    @property
    def worker_stats(self) -> Dict[str, Any]:
        """Simulator-side partition stats plus — after a step — the
        kernel's live per-worker DMA/event counters (the decentralized
        runtime's own accounting, read from the heap stats blocks)."""
        out = dict(Program.worker_stats.fget(self))
        if self.step_count > 0:
            per_worker = self.executor.worker_counters()
            out["kernel_workers"] = per_worker
            for k in ("event_waits", "event_wait_violations",
                      "event_signals"):
                out[k] = sum(d[k] for d in per_worker)
        return out

    def bind(self, params) -> "Program":
        """Pack weights into the heap and upload it — exactly once.  The
        prefill path uses ``params`` as given (f32 device arrays are kept,
        not copied), so the device holds the heap and one weight copy."""
        self._params_dev = jax.tree.map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        if self.cfg.embed_input:
            tok0 = np.zeros((self.batch, self.cfg.d_model), np.float32)
        else:
            tok0 = np.zeros((self.batch,), np.int32)
        # spans: the host heap image (weights copied to the host
        # included), then its upload
        with obs.span("bind.heap"):
            zero_cache = _np_tree(init_cache(self.cfg, self.batch,
                                             self.max_seq, dtype=jnp.float32))
            binds = decode_bindings(self.cfg, _np_tree(params), zero_cache,
                                    tok0, np.zeros((self.batch,), np.int32))
            heap = self.plan.build_heap(binds)
        with obs.span("bind.upload"):
            self.executor.upload(heap)
        return self

    def init_state(self) -> "Program":
        """Zero state slots in the resident heap (partial update, not a
        re-upload)."""
        self.executor.reset_state()
        return self

    def step(self, tokens_or_embeds, seq_lens, positions=None) -> np.ndarray:
        logits = self.executor.step(tokens_or_embeds, seq_lens, positions)
        self.step_count += 1
        return logits

    def reset_slot(self, slot: int) -> None:
        self.executor.reset_state(slot)

    def get_state(self):
        # gather of the state spans only — O(state), not O(heap) — all on
        # the device: a fresh snapshot of device arrays
        return self._jto_tree(self.executor.read_state())

    def set_state(self, state) -> None:
        # state-only scatter into the resident heap, on the device:
        # weights are never re-moved, so prefill/restore costs O(state)
        self.executor.write_state(self._jfrom_tree(state))


# ---------------------------------------------------------------------------
# The factory.
# ---------------------------------------------------------------------------

_BACKEND_CLASSES = {
    "jax": JaxProgram,
    "interpreter": InterpreterProgram,
    "megakernel": PallasProgram,
}


def compile(cfg, batch: int, max_seq: int, backend: str = "jax", *,
            step_cache: Optional[Dict[tuple, Callable]] = None,
            max_rows: Optional[int] = None, latency_aware: bool = True,
            event_fusion: bool = True, pipeline_depth: int = 2,
            num_workers: int = 1, tp: int = 1,
            trace: bool = False) -> Program:
    """Compile ``cfg``'s decode step once; returns a stateful
    :class:`Program` for ``backend`` ("jax" | "interpreter" |
    "megakernel").

    Compile options: ``max_rows`` caps decomposition tile rows (default:
    the backend's native choice — 8 register-friendly rows for the
    megakernel, the decomposer default otherwise),
    ``latency_aware``/``event_fusion`` toggle the scheduler/fusion passes
    (interpreter + megakernel), ``pipeline_depth`` sets the scheduler's
    producer→consumer separation target (2 = the megakernel's double
    buffer; see ``Program.pipeline_stats``), ``num_workers`` partitions
    the schedule onto W decentralized workers (per-worker descriptor
    streams + in-heap event counters on the megakernel; see
    ``Program.worker_stats`` — outputs are bitwise-identical across W),
    ``tp`` inserts AllReduce ops (paper §6.5) — on the interpreter
    backend they compile to graph stats, on the megakernel backend
    ``tp > 1`` stamps the plan into per-chip task tables whose
    collectives execute in-kernel as chunked ring-allreduce COMM tasks
    (``desc.stamp_multichip``; per-chip outputs are bitwise-identical
    across TP ∈ {1, 2, 4}).  ``step_cache`` shares (cfg, width)-keyed jitted
    prefill steps across programs.  ``trace=True`` enables the
    megakernel's heap-resident task trace ring (``Program.trace()``
    decodes it into an ``obs.TaskTrace``); trace-off programs are
    bitwise-identical to pre-trace builds.
    """
    if backend not in _BACKEND_CLASSES:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if num_workers < 1:
        raise ValueError(f"num_workers must be >= 1, got {num_workers}")
    if backend == "interpreter":
        dec = (DecomposeConfig() if max_rows is None
               else DecomposeConfig(max_rows=max_rows))
        opts = CompileOptions(
            decompose=dec,
            latency_aware_schedule=latency_aware,
            event_fusion=event_fusion,
            pipeline_depth=pipeline_depth,
            num_workers=num_workers,
            trace=trace)
        return InterpreterProgram(cfg, batch, max_seq, step_cache,
                                  options=opts, tp=tp)
    if backend == "megakernel":
        return PallasProgram(cfg, batch, max_seq, step_cache,
                             max_rows=8 if max_rows is None else max_rows,
                             latency_aware=latency_aware,
                             event_fusion=event_fusion,
                             pipeline_depth=pipeline_depth,
                             num_workers=num_workers, tp=tp, trace=trace)
    if tp != 1:
        raise ValueError(f"tp={tp} is only supported on the interpreter "
                         "and megakernel backends")
    return JaxProgram(cfg, batch, max_seq, step_cache,
                      pipeline_depth=pipeline_depth,
                      num_workers=num_workers)
