import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# the dry-run compiles against 512 *host* devices; never let jax autodetect
# a real accelerator (a stray libtpu would hijack backend init and crash)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production mesh, and extract the roofline terms from the
compiled artifact.

This proves the distribution config is coherent without real hardware:
sharding mismatches, OOM-at-compile and unsupported collectives all fail
here.  Results are written as one JSON per cell under ``runs/dryrun/``.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-110b --shape decode_32k
  python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np


def program_stats(arch: str, shape, num_workers: int = 4,
                  trace_prefix: Path | None = None) -> dict:
    """Compiler-side Program stats for a cell (``repro.api`` interpreter
    backend — no execution): task/event counts, the liveness-packed
    workspace footprint, and the W-worker runtime contract
    (``Program.worker_stats``: partition, event-counter cut, replayed
    makespan) at a serving-representative (batch, seq)."""
    from repro.api import compile as mpk_compile
    from repro.configs import get_config

    cfg = get_config(arch)
    batch = min(8, shape.global_batch)
    max_seq = min(1024, shape.seq_len)
    prog = mpk_compile(cfg, batch, max_seq, backend="interpreter",
                       num_workers=num_workers)
    rec = prog.describe()
    s = prog.stats
    rec["workspace_reuse_x"] = round(s["workspace_reuse_x"], 2)
    rec["fusion_reduction"] = round(s["fusion_reduction"], 1)
    ps = prog.pipeline_stats
    rec["pipeline_stalls"] = ps["stalls"]
    rec["pipeline_stalls_naive"] = ps["stalls_naive"]
    ws = prog.worker_stats
    rec["workers"] = {
        "num_workers": ws["num_workers"],
        "queue_lens": ws["queue_lens"],
        "cross_worker_deps": ws["cross_worker_deps"],
        "partition_steps": ws["partition_steps"],
        "sim_makespan_us": round(ws["sim_makespan_us"], 2),
        "worker_utilization": [round(u, 4)
                               for u in ws["worker_utilization"]],
    }
    if trace_prefix is not None:
        # compile-only cell: the dumpable timeline is the PREDICTED one
        from repro.obs import write_chrome_trace

        tl = prog.predicted_trace()
        write_chrome_trace(tl, f"{trace_prefix}.trace.json")
        Path(f"{trace_prefix}.snapshot.json").write_text(
            json.dumps(prog.metrics_snapshot(), indent=2))
        rec["trace_json"] = f"{trace_prefix}.trace.json"
        rec["predicted_makespan_s"] = tl.meta["makespan"]
        rec["trace_events"] = len(tl.events)
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path, microbatches: int = 1,
             dump_hlo: bool = False, overrides: dict | None = None,
             with_program_stats: bool = False,
             with_program_trace: bool = False) -> dict:
    # late imports: jax device count must be pinned first
    from repro.configs import SHAPES, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import cell_supported, input_specs
    from repro.roofline import TPU_V5E, parse_hlo_collectives, roofline_terms

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_tag = "multipod" if multi_pod else "pod"
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "step": shape.step, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
    }
    ok, why = cell_supported(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec
    if with_program_stats:
        rec["program"] = program_stats(
            arch, shape,
            num_workers=(overrides or {}).get("program_workers", 4),
            trace_prefix=(out_dir / f"{arch}_{shape_name}_{mesh_tag}"
                          if with_program_trace else None))

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    rec["chips"] = chips
    t0 = time.time()

    def compile_cell(layers=None, unroll=False):
        spec = input_specs(arch, shape_name, mesh,
                           microbatches=microbatches,
                           layers_override=layers, unroll=unroll,
                           overrides=overrides)
        jitted = jax.jit(
            spec["fn"],
            in_shardings=spec["in_shardings"],
            out_shardings=spec["out_shardings"],
            donate_argnums=spec["donate_argnums"],
        )
        with mesh:
            return jitted.lower(*spec["args"]).compile()

    # Full-depth compile: THE dry-run proof (sharding coherence + memory).
    compiled = compile_cell()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    if dump_hlo:
        (out_dir / f"{arch}_{shape_name}_{mesh_tag}.hlo").write_text(hlo)

    # Differential cost analysis: XLA's cost_analysis counts a scan body
    # ONCE regardless of trip count, so per-layer FLOPs/bytes/collectives
    # inside the layer scan are undercounted by ~n_layers.  Lower 1-block
    # and 2-block variants; the delta is the exact per-block cost, and
    # total = base + delta * (n_blocks - 1).
    p = cfg.block_period
    nb = cfg.n_layers // p

    def costs_of(c):
        cost = c.cost_analysis()
        coll = parse_hlo_collectives(c.as_text())
        return (float(cost.get("flops", 0.0)),
                float(cost.get("bytes accessed", 0.0)),
                float(coll["wire_bytes"]), coll)

    if nb > 1:
        c1 = compile_cell(layers=p, unroll=True)
        c2 = compile_cell(layers=2 * p, unroll=True)
        f1, b1, w1, k1 = costs_of(c1)
        f2, b2, w2, k2 = costs_of(c2)
        flops = f1 + (f2 - f1) * (nb - 1)
        hbm_bytes = b1 + (b2 - b1) * (nb - 1)
        wire = w1 + (w2 - w1) * (nb - 1)
        per_kind = {
            kind: k1["per_kind"].get(kind, 0.0)
            + (k2["per_kind"].get(kind, 0.0)
               - k1["per_kind"].get(kind, 0.0)) * (nb - 1)
            for kind in set(k1["per_kind"]) | set(k2["per_kind"])
        }
        coll = {"wire_bytes": wire, "per_kind": per_kind,
                "num_ops": parse_hlo_collectives(hlo)["num_ops"]}
        rec["cost_extrapolated"] = True
    else:
        flops, hbm_bytes, wire, coll = costs_of(compiled)
        rec["cost_extrapolated"] = False
    t_lower = 0.0
    t_compile = time.time() - t0

    # useful model FLOPs per device
    n_active = cfg.active_param_count()
    tokens = (shape.global_batch * shape.seq_len
              if shape.step in ("train", "prefill") else shape.global_batch)
    mult = 6 if shape.step == "train" else 2
    model_flops = mult * n_active * tokens / chips

    # memory term: analytical minimal-traffic model (HLO "bytes accessed"
    # has no fusion model on the CPU backend and overcounts 10-50×; it is
    # recorded as hbm_bytes_hlo for reference).
    from repro.roofline.traffic import hbm_traffic_bytes
    spec0 = input_specs(arch, shape_name, mesh, overrides=overrides)
    policy = spec0["policy"]
    traffic = hbm_traffic_bytes(
        cfg, shape, chips=chips, tp=policy.tp_size,
        fsdp_gathered=bool(policy.fsdp_axes),
        kv_bytes=(overrides or {}).get("kv_dtype_bytes", 2),
        masked_cache_update=policy.masked_cache_update)
    rec["overrides"] = overrides or {}

    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": flops,
        "hbm_bytes_per_device": traffic,
        "hbm_bytes_hlo": hbm_bytes,
        "collective_wire_bytes": coll["wire_bytes"],
        "collective_ops": coll["num_ops"],
        "collective_per_kind": coll["per_kind"],
        "roofline": roofline_terms(
            flops, traffic, coll["wire_bytes"],
            model_flops_per_device=model_flops),
    })
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        try:
            rec[attr] = int(getattr(mem, attr))
        except Exception:
            pass
    # HBM fit check: arguments (params+opt+cache shards) + temps per device
    try:
        resident = rec["argument_size_in_bytes"] + rec["temp_size_in_bytes"]
        rec["resident_bytes_per_device"] = resident
        rec["fits_hbm"] = bool(resident < TPU_V5E.hbm_bytes)
    except KeyError:
        pass
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--dump-hlo", action="store_true")
    ap.add_argument("--no-sp", action="store_true",
                    help="disable Megatron-SP residual sharding")
    ap.add_argument("--pure-dp", action="store_true",
                    help="fold the model axis into data parallelism")
    ap.add_argument("--kv-bytes", type=int, default=2,
                    help="KV cache element bytes (1 = fp8 cache)")
    ap.add_argument("--masked-update", action="store_true",
                    help="decode cache write as masked rewrite (no scatter)")
    ap.add_argument("--q-replicate", action="store_true",
                    help="replicate q heads in decode (seq-sharded cache)")
    ap.add_argument("--moe-2d", action="store_true",
                    help="2D expert GEMM (weights never move) for decode")
    ap.add_argument("--tag", default="",
                    help="suffix for the result json (perf variants)")
    ap.add_argument("--program-stats", action="store_true",
                    help="record repro.api Program compiler stats (tasks/"
                         "events/workspace + worker partition / event "
                         "counters) in each cell json")
    ap.add_argument("--program-workers", type=int, default=4,
                    help="worker width the --program-stats compile "
                         "partitions onto")
    ap.add_argument("--program-trace", action="store_true",
                    help="with --program-stats: also write the predicted "
                         "task timeline (<cell>.trace.json, Chrome-trace "
                         "format) and the metrics snapshot "
                         "(<cell>.snapshot.json) per cell")
    args = ap.parse_args()
    overrides = {}
    if args.no_sp:
        overrides["shard_seq"] = False
    if args.pure_dp:
        overrides["pure_dp"] = True
    if args.kv_bytes != 2:
        overrides["kv_dtype_bytes"] = args.kv_bytes
    if args.masked_update:
        overrides["masked_cache_update"] = True
    if args.q_replicate:
        overrides["q_head_replicate"] = True
    if args.moe_2d:
        overrides["moe_2d"] = True
    if args.program_workers != 4:
        overrides["program_workers"] = args.program_workers

    from repro.configs import SHAPES, list_archs

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'multipod' if mp else 'pod'}"
                if args.tag:
                    tag += f"_{args.tag}"
                try:
                    rec = run_cell(arch, shape, mp, out_dir,
                                   microbatches=args.microbatches,
                                   dump_hlo=args.dump_hlo,
                                   overrides=overrides or None,
                                   with_program_stats=(args.program_stats
                                                       or args.program_trace),
                                   with_program_trace=args.program_trace)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multipod" if mp else "pod",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    failures += 1
                (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=2))
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" dom={r['dominant']}"
                             f" bound={r['roofline_bound_s']*1e3:.2f}ms"
                             f" fit={rec.get('fits_hbm')}")
                elif status == "error":
                    extra = " " + rec["error"][:120]
                print(f"[dryrun] {tag}: {status}{extra}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
