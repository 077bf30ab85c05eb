"""``BENCHMARK.json`` and the files it names, each found by its name.

A configuration is ``configs/<name>.json``, a traffic mix
``traffic/<name>.json``, a per-layer metric ``metrics/<name>.py`` (a
module with ``read(record) -> float | None``), all under the benchmark's
directory; the plain reference a configuration names is
``models/<family>.py``.  Adding a cell or a metric adds files and
entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Callable, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    end_to_end: List[dict]      # the metrics this cell reports untraced
    per_layer: List[dict]       # ... and traced


def load(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(manifest: dict, name: str) -> Cell:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return Cell(name, w["config"], w["traffic"], int(w["chips"]),
                        [m for m in manifest["end_to_end"]
                         if _applies(m, name)],
                        [m for m in manifest["per_layer"]
                         if _applies(m, name)])
    known = ", ".join(w["name"] for w in manifest["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def config(name: str, bench: Path = BENCH) -> dict:
    return _json(Path(bench) / "configs" / f"{name}.json")


def traffic(name: str, bench: Path = BENCH) -> dict:
    return _json(Path(bench) / "traffic" / f"{name}.json")


def peaks(bench: Path = BENCH) -> dict:
    return _json(Path(bench) / "peaks.json")["devices"]


def model(family: str):
    """The reference module of a family (``models/<family>.py``)."""
    return importlib.import_module(f"bench.models.{family}")


def reader(name: str, bench: Path = BENCH) -> Callable:
    """``read`` of ``metrics/<name>.py``."""
    path = Path(bench) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
