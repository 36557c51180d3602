"""An operation is two files found by the mix's ``op``: one added as files
alone is posted, collected and compared; the references import nothing of
the simulator; and the operations of each cell draw, model and compare
exactly what its record (``tests/bench/data/fingerprints/<cell>.json``)
holds."""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec
from tests.bench import fingerprint as fp

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

# a contiguous byte message from rank src to rank dst
BYTE_SEND_OP = '''
import numpy as np


def post(comm, mix, ids, inputs):
    buf = np.zeros(inputs.size, np.uint8)
    reqs = [comm.irecv(mix["dst"], buf, source=mix["src"], tag=mix["tag"]),
            comm.isend(mix["src"], mix["dst"], inputs.copy(),
                       tag=mix["tag"])]
    return reqs, buf


def outputs(posted):
    return posted[1].copy()
'''
BYTE_SEND_CHECK = '''
import numpy as np


def draw(mix, config, rng):
    return rng.integers(0, 256, mix["bytes"], dtype=np.uint8)


def expected(mix, config, inputs):
    return inputs.copy()


def compare(mix, config, inputs, outputs):
    got = np.asarray(outputs, np.uint8).reshape(-1)
    if got.size != inputs.size:
        return {"bytes_wrong": float(inputs.size)}
    return {"bytes_wrong": float(np.count_nonzero(got != inputs))}


def control(mix, config, inputs):
    return inputs & 0xFE


def small(mix, config):
    return mix, config
'''


def test_added_operation_is_found_and_checked(bench_root, run_cpu):
    b = bench_root / "bench"
    (b / "ops" / "byte_send.py").write_text(BYTE_SEND_OP)
    (b / "checks" / "byte_send.py").write_text(BYTE_SEND_CHECK)
    (b / "configs" / "pair_2r.json").write_text(json.dumps(dict(
        name="pair_2r", ranks=2, mpi={"batch": 8}, link={"latency": 1},
        datatypes=[], reduced={})))
    (b / "traffic" / "8KiB_loss2.json").write_text(json.dumps(dict(
        op="byte_send", bytes=8192, src=0, dst=1, tag=3, loss=0.02,
        loss_seed=1, warm_frames=8, limits={"bytes_wrong": 0})))
    bench = json.loads((bench_root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(
        name="pair_2r", source="https://example.org/pair",
        file="bench/configs/pair_2r.json", reduced=[], why="test"))
    bench["workloads"].append(dict(
        name="pair_2r.8KiB_loss2", config="pair_2r", traffic="8KiB_loss2",
        chips=1, why="test"))
    (bench_root / "BENCHMARK.json").write_text(json.dumps(bench))

    result, _, err = run_cpu(bench_root, "pair_2r.8KiB_loss2", seconds=1.5)
    assert result["correct"] and result["failed"] == 0, err
    assert result["attempted"] >= 1
    assert result["compared"] == {"bytes_wrong": {"value": 0.0, "limit": 0}}
    assert err.strip().splitlines()[-1] == "compared bytes_wrong 0.0 limit 0"


def test_unknown_operation_is_refused_by_its_file(bench_root):
    w = BENCH["workloads"][0]
    mix_file = bench_root / "bench" / "traffic" / f"{w['traffic']}.json"
    mix = json.loads(mix_file.read_text())
    mix_file.write_text(json.dumps(dict(mix, op="no_such_op")))
    missing = bench_root / "bench" / "checks" / "no_such_op.py"
    with pytest.raises(FileNotFoundError, match=str(missing)):
        spec.load(bench_root, w["name"])


def test_references_import_nothing_of_the_simulator():
    code = (
        "import sys\nfrom pathlib import Path\nfrom bench import spec\n"
        "ops = sorted(p.stem for p in Path('bench/checks').glob('*.py'))\n"
        "for op in ops:\n    spec.load_check(Path('.'), op)\n"
        "print(len(ops), sorted(m for m in sys.modules\n"
        "                       if m.split('.')[0] in ('repro', 'jax')))\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120,
        env={"PYTHONPATH": f"{ROOT}:{ROOT / 'src'}", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stderr
    n, loaded = proc.stdout.split(" ", 1)
    assert int(n) == len({json.loads(
        (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        ["op"] for w in BENCH["workloads"]})
    assert loaded.strip() == "[]"


def _record(data: Path, workload: str) -> dict:
    """The recorded fingerprints of ``workload``, per seed; a cell with no
    record fails, naming the file and the command that writes it."""
    path = data / f"{workload}.json"
    if not path.is_file():
        pytest.fail(f"no fingerprint record at {path}: write it with "
                    f"`{fp.command(workload)}`", pytrace=False)
    return json.loads(path.read_text())["seeds"]


def test_missing_record_names_its_path_and_command(tmp_path):
    with pytest.raises(pytest.fail.Exception) as failed:
        _record(tmp_path, "new_4r.64KiB_loss1")
    msg = str(failed.value)
    assert str(tmp_path / "new_4r.64KiB_loss1.json") in msg
    assert ("python3 tests/bench/fingerprint.py --workload "
            "new_4r.64KiB_loss1") in msg


@pytest.mark.parametrize("workload", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_cell_repeats_what_was_recorded(bench_root, workload):
    """Seeds 1 and 2 at the ``small`` size draw byte-identical inputs and
    give identical outputs, modelled statistics and numbers compared to
    those of the cell's record."""
    want = _record(fp.DATA, workload["name"])
    assert fp.fingerprints(bench_root, workload) == want
