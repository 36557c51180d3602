"""The benchmark is driven by its data: every cell resolves to its files, and
a configuration, a traffic mix and a metric added as files are found without
an edit to the harness."""
from __future__ import annotations

import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_resolves_to_its_files(workload):
    cell = spec.load(ROOT, workload)
    w = next(w for w in BENCH["workloads"] if w["name"] == workload)
    assert cell.config["name"] == w["config"]
    assert cell.chips == 1
    op = cell.mix["op"]
    assert cell.op.__file__ == str(ROOT / "bench" / "ops" / f"{op}.py")
    assert cell.check.__file__ == str(ROOT / "bench" / "checks" / f"{op}.py")
    assert all(callable(getattr(cell.op, f)) for f in ("post", "outputs"))
    assert all(callable(getattr(cell.check, f)) for f in (
        "draw", "expected", "compare", "control", "small"))
    assert set(cell.mix["limits"])
    assert {m.name for m in cell.end_to_end} == {
        m["name"] for m in BENCH["end_to_end"]}
    assert {m.name for m in cell.per_layer} == {
        m["name"] for m in BENCH["per_layer"]
        if workload in m.get("workloads", [workload])}
    assert all(callable(m.read) for m in cell.end_to_end + cell.per_layer)


def test_benchmark_json_keeps_to_its_form():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for entry in (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                  + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        assert set(c["reduced"]) == set(
            json.loads((ROOT / c["file"]).read_text())["reduced"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    moves = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in moves for m in BENCH["per_layer"])
    assert len({w["name"] for w in BENCH["workloads"]}) == len(CELLS)


def test_added_config_traffic_and_metric_are_found(bench_root, run_cpu):
    (bench_root / "bench" / "configs" / "tiny_2r.json").write_text(
        json.dumps(dict(
            name="tiny_2r", ranks=2, mpi={"batch": 8}, link={"latency": 1},
            datatypes=[], reduced={})))
    # an existing mix at another size and loss rate
    mix = json.loads(
        (bench_root / "bench" / "traffic" / "2KiB_loss2.json").read_text())
    mix.update(bytes_per_rank=4096, loss=0.0, warm_frames=8,
               limits={"sum_gap": 1e-5})
    (bench_root / "bench" / "traffic" / "4KiB_loss0.json").write_text(
        json.dumps(mix))
    (bench_root / "bench" / "metrics" / "ticks_in_window.py").write_text(
        "def read(run):\n    return float(run.ticks)\n")
    bench = json.loads((bench_root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(
        name="tiny_2r", source="https://example.org/tiny",
        file="bench/configs/tiny_2r.json", reduced=[], why="test"))
    bench["workloads"].append(dict(
        name="tiny_2r.4KiB_loss0", config="tiny_2r", traffic="4KiB_loss0",
        chips=1, why="test"))
    bench["per_layer"].append(dict(
        name="ticks_in_window", unit="ticks", better="higher",
        source="host_clock", layer="fabric tick", moves="ticks_per_s",
        workloads=["tiny_2r.4KiB_loss0"]))
    # an existing metric that lists its cells is given the new one
    next(m for m in bench["per_layer"] if m["name"] == "compile_s")[
        "workloads"].append("tiny_2r.4KiB_loss0")
    (bench_root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load(bench_root, "tiny_2r.4KiB_loss0")
    assert "ticks_in_window" in [m.name for m in cell.per_layer]
    result, _, _ = run_cpu(bench_root, "tiny_2r.4KiB_loss0", trace=1)
    assert result["correct"] and result["attempted"] >= 1
    assert result["metrics"]["ticks_in_window"]["value"] > 0
    assert result["metrics"]["compile_s"]["unit"] == "s"


def test_unknown_workload_is_refused(bench_root):
    with pytest.raises(KeyError, match="no workload"):
        spec.load(bench_root, "nothing.here")


def test_harness_refuses_the_cpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert not proc.stdout.strip()


def test_harness_needs_the_program(bench_root):
    """Run from a directory that holds only the benchmark's own files, it
    fails before any result."""
    import shutil
    shutil.copytree(ROOT / "bench", bench_root / "bench",
                    dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=bench_root, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "repro" in proc.stderr
    assert not proc.stdout.strip()
