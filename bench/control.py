"""Readings of the program and of its lower-precision control, per seed.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

For each seed this builds the cell as ``run.py`` does, runs its closed loop
for ``--seconds`` (the harness's window, untimed here), and compares every
completed operation twice with the reference: the program's outputs, and
the control's, which is the reference computed one precision step lower
(the operation's ``check.control``) put in the program's place.  It prints one
JSON line per seed with the worst reading of each number for both, and a
last line with, per number, the largest program reading and the smallest
control reading: the two readings a limit is set between.  One process
serves every seed, so the programs compile once.  The benchmark's own runs
never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import generator, run, spec  # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    traffic = generator.build(cell, seed)
    traffic.warm_up()
    _, tick_s, _, done = run.measure(traffic, seconds)
    program, control = {}, {}
    for d in done:
        if d.error:
            raise RuntimeError(f"seed {seed}: operation {d.index} failed: "
                               f"{d.error}")
        low = cell.check.control(cell.mix, cell.config, d.inputs)
        for out, worst in ((d.outputs, program), (low, control)):
            for name, v in cell.check.compare(cell.mix, cell.config,
                                              d.inputs, out).items():
                worst[name] = max(worst.get(name, 0.0), v)
    return dict(seed=seed, ops=len(done), ticks=len(tick_s),
                program=program, control=control)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, at least three")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load(ROOT, args.workload)
    run.require_tpu(cell.chips)
    run.use_compile_cache(ROOT)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(cell, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    names = cell.mix["limits"]
    print(json.dumps(dict(
        workload=cell.name, seeds=len(rows),
        ops=sum(r["ops"] for r in rows),
        program_max={n: max(r["program"].get(n, 0.0) for r in rows)
                     for n in names},
        control_min={n: min(r["control"].get(n, 0.0) for r in rows)
                     for n in names},
        limits=names)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
