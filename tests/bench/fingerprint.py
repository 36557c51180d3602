"""Records what a cell's operations draw, model and compare on the CPU.

    python3 tests/bench/fingerprint.py --workload <cell>

writes ``tests/bench/data/fingerprints/<cell>.json``: for seeds 1 and 2, at
the size the operation's ``small`` gives, the warm-up ticks and, of the
first two operations after the warm-up, the digests of their inputs and
outputs, their modelled statistics and the numbers compared.  The test
``test_cell_repeats_what_was_recorded`` runs the same loop and asks for the
same record, so a cell's record, once written, guards its modelled
statistics against a change to the harness or the program.  A cell added
to ``BENCHMARK.json`` adds its record with this command.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import generator, spec  # noqa: E402
from tests.bench.conftest import copy_definition  # noqa: E402
from tests.bench.test_bench_traffic import _shrink  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "fingerprints"
SEEDS = (1, 2)
NOTE = ("tests/bench/fingerprint.py on the CPU: seeds 1 and 2 at the size "
        "that tests/bench/test_bench_traffic._shrink gives, the first two "
        "operations after the warm-up")


def command(workload: str) -> str:
    """The command that writes the record of ``workload``."""
    return f"python3 tests/bench/fingerprint.py --workload {workload}"


def _digest(x) -> str:
    h = hashlib.sha256()
    for a in (x if isinstance(x, list) else [x]):
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def fingerprint(cell, seed: int, n_ops: int = 2) -> dict:
    """Warm-up ticks, and of the first operations of a run: digests of
    their inputs and outputs, their modelled statistics and the numbers
    compared, as the harness's closed loop makes them."""
    traffic = generator.build(cell, seed)
    out = dict(warmup_ticks=traffic.warm_up(), ops=[])
    for index in range(n_ops):
        op = traffic.post(index)
        for _ in range(1_000_000):
            traffic.comm.progress(1)
            if op.finished():
                break
        d = op.complete()
        out["ops"].append(dict(
            inputs=_digest(d.inputs), outputs=_digest(d.outputs),
            error=d.error, modelled=d.modelled,
            compared=cell.check.compare(cell.mix, cell.config, d.inputs,
                                        d.outputs)))
    return out


def fingerprints(root: Path, workload: dict) -> dict:
    """Per seed, the fingerprint of the cell ``workload`` (its
    ``BENCHMARK.json`` entry) in the checkout ``root``, after its
    configuration and mix there are written over at the CPU size."""
    _shrink(root, workload)
    cell = spec.load(root, workload["name"])
    return {str(seed): fingerprint(cell, seed) for seed in SEEDS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    # the record is of the CPU's arithmetic, which the tests run on
    jax.config.update("jax_platforms", "cpu")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    workload = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
    if workload is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    with tempfile.TemporaryDirectory() as tmp:
        seeds = fingerprints(copy_definition(Path(tmp)), workload)
    DATA.mkdir(parents=True, exist_ok=True)
    path = DATA / f"{args.workload}.json"
    path.write_text(json.dumps(dict(recorded=NOTE, seeds=seeds), indent=1,
                               sort_keys=True) + "\n")
    print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
