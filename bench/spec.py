"""Resolves a cell of ``BENCHMARK.json`` to the files that define it.

A cell names a configuration, whose file the ``configs`` entry gives, and a
traffic mix, found as ``bench/traffic/<traffic>.json``.  The mix's ``op``
names its operation, two modules found by that name:

* ``bench/ops/<op>.py``, the program side: ``post(comm, mix, ids, inputs)``
  posts the operation's requests on the communicator and returns the pair
  ``(requests, held)``, every request with ``.done`` and ``.error``;
  ``outputs(posted)`` takes that pair and returns the outputs as they are
  at completion;
* ``bench/checks/<op>.py``, the reference side, which imports nothing of the
  simulator: ``draw(mix, config, rng)`` gives the inputs from the seed's
  generator, ``expected(mix, config, inputs)`` the reference's outputs,
  ``compare(mix, config, inputs, outputs)`` the numbers compared as
  ``{name: value}``, ``control(mix, config, inputs)`` the reference's
  outputs one precision step lower, and ``small(mix, config)`` the mix and
  configuration at the size the CPU tests run.

Each metric, end to end or per layer, is read by ``bench/metrics/<name>.py``,
a module with one function ``read(run)`` that returns a number, or ``None``
where the run holds nothing for it to read.  Adding a configuration, a mix,
an operation or a metric is adding its files and its entry; nothing here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
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
    op: ModuleType
    check: ModuleType
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _module(path: Path, name: str, what: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {what} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(root: Path, entry: dict) -> Metric:
    name = entry["name"]
    mod = _module(root / "bench" / "metrics" / f"{name}.py",
                  f"bench_metric_{name.replace('.', '_')}",
                  f"reader for metric {name!r}")
    return Metric(name, entry["unit"], mod.read)


def load_check(root: Path, op: str) -> ModuleType:
    """The reference side of operation ``op``."""
    return _module(root / "bench" / "checks" / f"{op}.py",
                   f"bench_check_{op.replace('.', '_')}",
                   f"reference for operation {op!r}")


def load_op(root: Path, op: str) -> ModuleType:
    """The program side of operation ``op``."""
    return _module(root / "bench" / "ops" / f"{op}.py",
                   f"bench_op_{op.replace('.', '_')}",
                   f"program side of operation {op!r}")


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
        check=load_check(root, mix["op"]), op=load_op(root, mix["op"]),
        end_to_end=[load_metric(root, m) for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[load_metric(root, m) for m in bench["per_layer"]
                   if _applies(m, workload)])
