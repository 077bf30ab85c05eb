"""BENCHMARK.json, the files it names, the result line, and the exits
that print no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import manifest, run

ROOT = Path(__file__).resolve().parents[2]


def test_every_name_resolves_to_a_file():
    m = manifest.load()
    for w in m["workloads"]:
        c = manifest.cell(m, w["name"])
        conf = manifest.config(c.config)
        assert conf["name"] == c.config
        manifest.traffic(c.traffic)
        manifest.model(conf["family"])
        assert any(x["name"] == "setup_s" for x in c.end_to_end)
        assert len(c.end_to_end) >= 2 and c.per_layer
        for metric in c.per_layer:
            assert callable(manifest.reader(metric["name"]))
            moved = [x for x in c.end_to_end if x["name"] == metric["moves"]]
            assert moved, (metric["name"], w["name"])
    for conf in m["configs"]:
        assert (ROOT / conf["file"]).is_file()


def test_program_config_matches_the_file():
    m = manifest.load()
    for conf in m["configs"]:
        c = manifest.config(conf["name"])
        model, cfg = run.build_program(c)
        assert cfg.n_layers == c["program"]["overrides"]["n_layers"]


def test_dropped_in_files_are_found_by_name(tmp_path):
    for sub in ("configs", "traffic", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "new-model.L2.json").write_text(
        json.dumps({"name": "new-model.L2", "family": "llama"}))
    (tmp_path / "traffic" / "new-mix.json").write_text(
        json.dumps({"loop": "closed", "clients": 2}))
    (tmp_path / "metrics" / "new_metric.cell.py").write_text(
        "def read(record):\n    return 42.0\n")
    assert manifest.config("new-model.L2", tmp_path)["family"] == "llama"
    assert manifest.traffic("new-mix", tmp_path)["clients"] == 2
    assert manifest.reader("new_metric.cell", tmp_path)(None) == 42.0
    m = {"workloads": [{"name": "x.y", "config": "new-model.L2",
                        "traffic": "new-mix", "chips": 1}],
         "end_to_end": [{"name": "setup_s"},
                        {"name": "a", "workloads": ["other"]}],
         "per_layer": [{"name": "new_metric.cell", "workloads": ["x.y"]}]}
    c = manifest.cell(m, "x.y")
    assert [e["name"] for e in c.end_to_end] == ["setup_s"]
    assert [p["name"] for p in c.per_layer] == ["new_metric.cell"]


def test_result_line_schema(capsys):
    res = {"correct": True, "attempted": 8, "failed": 0,
           "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 1},
           "breakdown": {"device_ops": [["k", 1.0]], "idle_gaps": []},
           "checks": {"logit_err": {"value": 1e-6, "limit": 1e-5}}}
    run.emit(res)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    assert err.strip().splitlines()[-1].startswith("check logit_err:")


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "deepseek7b.decode",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "repro" in p.stderr          # the system under test is missing


def test_peaks_know_the_v5e_and_nothing_else_is_a_default():
    peaks = manifest.peaks()
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == pytest.approx(197e12)
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == pytest.approx(819e9)
    assert "cpu" not in peaks


def test_stalls_name_the_slowest_iteration_and_the_collector():
    import gc

    from bench.serve import Call, Iteration

    its = [Iteration(0.0, 0.2, [Call("step", 0.01, 0.19, [[5]])]),
           Iteration(0.2, 2.7, [Call("prefill", 0.21, 2.6, [[0, 1]]),
                                Call("step", 2.6, 2.69, [[6]])]),
           Iteration(3.0, 3.2, [])]
    pauses = []
    cb = run._gc_timer(pauses)
    gc.callbacks.append(cb)
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(cb)
    assert pauses and pauses[-1][0] == 2
    line = run._stalls(its, pauses)
    assert line.startswith("slowest iteration 2500.0 ms (prefill 2390.0, "
                           "step 90.0)")
    assert "longest between iterations 300.0 ms" in line
    assert f"gc {len(pauses)} collections" in line
    assert run._stalls([], []) == "no iterations"
