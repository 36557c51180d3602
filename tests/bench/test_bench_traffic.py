"""Each traffic mix of BENCHMARK.json, run by the harness on the CPU at a
small size, passes its comparison with the plain reference."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _shrink(root: Path, workload: dict) -> None:
    """Writes the cell's configuration and mix in ``root`` over with the
    CPU-test size that its operation's ``small`` gives."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg_file = next(root / c["file"] for c in bench["configs"]
                    if c["name"] == workload["config"])
    mix_file = root / "bench" / "traffic" / f"{workload['traffic']}.json"
    cfg = json.loads(cfg_file.read_text())
    mix = json.loads(mix_file.read_text())
    mix, cfg = spec.load_check(root, mix["op"]).small(mix, cfg)
    cfg_file.write_text(json.dumps(cfg))
    mix_file.write_text(json.dumps(mix))


@pytest.mark.parametrize("workload", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_mix_runs_and_passes_its_check(bench_root, run_cpu, workload):
    _shrink(bench_root, workload)
    result, out, err = run_cpu(bench_root, workload["name"], seconds=2)
    assert result["correct"], err
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    limits = json.loads((bench_root / "bench" / "traffic"
                         / f"{workload['traffic']}.json").read_text())
    for name, c in result["compared"].items():
        assert c["limit"] == limits["limits"][name]
        assert c["value"] <= c["limit"]
        assert f"compared {name} " in err.strip().splitlines()[-len(
            result["compared"]):][list(result["compared"]).index(name)]
    lines = [json.loads(x) for x in out.strip().splitlines()
             if x.startswith("{")]
    assert lines[1]["compiles_in_window"] == 0
    assert lines[2]["modelled_total"]["ticks"] > 0
    assert set(result["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}


def test_modelled_statistics_do_not_depend_on_the_seed(bench_root, run_cpu):
    """The seed draws the values; the fabric does the same work."""
    cell = "ddt_fig10_2r.complex_loss5"
    per_op = []
    for seed in (5, 2**33 + 1):
        run_cpu(bench_root, cell, seed=seed, seconds=3)
        out = json.loads((bench_root / ".bench_out"
                          / f"{cell}.seed{seed}.trace0.json").read_text())
        per_op.append(out["modelled"])
    n = min(len(m) for m in per_op)
    assert n >= 2 and per_op[0][:n] == per_op[1][:n]
