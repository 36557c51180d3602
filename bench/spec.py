"""Resolves a cell of ``BENCHMARK.json`` to the files that define it.

A cell names a configuration, whose file the ``configs`` entry gives, and a
traffic mix, found as ``bench/traffic/<traffic>.json``.  Each metric, end to
end or per layer, is read by ``bench/metrics/<name>.py``, a module with one
function ``read(run)`` that returns a number, or ``None`` where the run holds
nothing for it to read.  Adding a configuration, a mix or a metric is adding
its file and its entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_metric(root: Path, entry: dict) -> Metric:
    path = root / "bench" / "metrics" / f"{entry['name']}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{entry['name'].replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {entry['name']!r} "
                                f"at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return Metric(entry["name"], entry["unit"], mod.read)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load(root: Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have: {', '.join(sorted(cells))})")
    w = cells[workload]
    configs: Dict[str, dict] = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=workload, chips=w["chips"], config=config, mix=mix,
        end_to_end=[load_metric(root, m) for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[load_metric(root, m) for m in bench["per_layer"]
                   if _applies(m, workload)])
