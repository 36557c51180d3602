"""Runs one cell of the chip benchmark of the MPI-over-sPIN simulator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` is one configuration under one traffic mix
(``bench/spec.py`` finds their files).  The run:

1. refuses to go on, with a non-zero exit and no result, unless JAX's first
   device is a TPU and there are as many as the cell's chips;
2. set-up: builds the cell's ``Communicator``, warms up every program the
   window runs (``generator.Traffic.warm_up``) with JAX's persistent
   compilation cache in ``.jax_cache/`` of the checkout;
3. the window: a closed loop with one operation outstanding.  It posts an
   operation, ticks the fabric with ``comm.progress(1)``, each call timed on
   the host clock, until the operation completes, and posts the next at
   once.  When ``--seconds`` have passed it waits for every live device array;
   the operation still in flight is not counted, its ticks are;
4. the check: every operation completed in the window is compared with the
   plain reference of the mix's operation (``bench/checks/<op>.py``)
   against the mix's limits.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries the
per-layer metrics, the device's busy and window seconds and a breakdown.
Earlier lines give the set-up, the compiles inside the window (there should
be none) and the modelled statistics of the completed operations, which
depend on the seed alone; ``.bench_out/`` keeps them per operation with
every tick's time.  The numbers compared are the last lines on standard
error; the last line on standard output is the result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import generator, spec  # noqa: E402
from bench import trace as tracelib  # noqa: E402

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Compile seconds and compiles, from ``jax.monitoring`` events; a
    program found in the persistent cache counts the time to load it."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def on_duration(self, event: str, secs: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.seconds += secs
            self.count += 1


@dataclasses.dataclass
class Run:
    """What the metric readers in ``bench/metrics/`` read."""
    tick_s: List[float]
    window_s: float
    setup_s: float
    setup_compile_s: float
    trace: Optional[tracelib.Trace] = None

    @property
    def ticks(self) -> int:
        return len(self.tick_s)


def require_tpu(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU, but JAX's first device is on platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}); no result")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}; no result")
    return devs[:chips]


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, so only a cell's first run there compiles; programs under a
    second are cached too."""
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def measure(traffic, seconds: float):
    """The closed loop of the window; returns tick times, window seconds
    and the completed operations."""
    comm = traffic.comm
    tick_s: List[float] = []
    done = []
    op, index = None, 0
    clock = time.perf_counter
    t0 = clock()
    deadline = t0 + seconds
    with jax.profiler.TraceAnnotation("window"):
        while clock() < deadline:
            if op is None:
                with jax.profiler.TraceAnnotation("post"):
                    op = traffic.post(index)
            with jax.profiler.TraceAnnotation("tick"):
                a = clock()
                comm.progress(1)
                tick_s.append(clock() - a)
            if op.finished():
                with jax.profiler.TraceAnnotation("complete"):
                    done.append(op.complete())
                if done[-1].error:
                    break
                op, index = None, index + 1
        jax.block_until_ready(jax.live_arrays())
        window_s = clock() - t0
    return t0, tick_s, window_s, done


def check(cell, done):
    """Compares every completed operation with the reference; returns the
    failed count and, per number compared, the worst reading and limit."""
    limits = cell.mix["limits"]
    worst = {name: 0.0 for name in limits}
    failed = 0
    for d in done:
        bad = d.error is not None
        if not bad:
            for name, value in cell.check.compare(
                    cell.mix, cell.config, d.inputs, d.outputs).items():
                # JSON has no infinity: a result that cannot be compared
                # reads as the largest float
                value = min(value, sys.float_info.max)
                worst[name] = max(worst[name], value)
                bad |= not value <= limits[name]
        failed += bad
    return failed, {name: {"value": worst[name], "limit": limits[name]}
                    for name in limits}


def _summary(done) -> dict:
    keys = done[0].modelled.keys() if done else ()
    return {k: int(sum(d.modelled[k] for d in done)) for k in keys}


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load(root, args.workload)
    devs = require_tpu(cell.chips)
    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter.on_duration)
    try:
        result = _run(cell, devs, meter, args, root)
    finally:
        jax.monitoring.unregister_event_duration_listener(meter.on_duration)
    print(json.dumps(result), flush=True)
    return 0


def _run(cell, devs, meter, args, root: Path) -> dict:
    use_compile_cache(root)

    with jax.profiler.TraceAnnotation("setup"):
        traffic = generator.build(cell, args.seed)
        warm_ticks = traffic.warm_up()
    setup_compile_s, setup_compiles = meter.seconds, meter.count
    print(json.dumps(dict(
        workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, warmup_ticks=warm_ticks,
        setup_compile_s=setup_compile_s, setup_compiles=setup_compiles)),
        flush=True)

    out_dir = root / ".bench_out"
    trace_dir = out_dir / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        tracelib.start(trace_dir)
    t0, tick_s, window_s, done = measure(traffic, args.seconds)
    if args.trace:
        tracelib.stop()
    compiles_in_window = meter.count - setup_compiles
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    del traffic
    gc.collect()

    with jax.profiler.TraceAnnotation("check"):
        failed, compared = check(cell, done)
    run = Run(tick_s=tick_s, window_s=window_s, setup_s=t0 - T_START,
              setup_compile_s=setup_compile_s)
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(jax.devices()), memory_peak_bytes=memory_peak)
    result = dict(correct=bool(done) and failed == 0, attempted=len(done),
                  failed=failed)
    if args.trace:
        run.trace = tracelib.Trace.from_dir(trace_dir)
        metrics = cell.per_layer
        device.update(busy_s=run.trace.busy_s(), window_s=run.trace.window_s)
    else:
        metrics = cell.end_to_end
    values = {m.name: (m.read(run), m.unit) for m in metrics}
    result["metrics"] = {name: {"value": v, "unit": unit}
                         for name, (v, unit) in values.items()
                         if v is not None}
    result["device"] = device
    if args.trace:
        result["breakdown"] = run.trace.breakdown()
    result["compared"] = compared

    modelled = [dict(op=d.index, error=d.error, **d.modelled) for d in done]
    print(json.dumps(dict(compiles_in_window=compiles_in_window,
                          ticks=run.ticks, window_s=window_s)), flush=True)
    print(json.dumps(dict(modelled_total=_summary(done),
                          modelled_first_ops=modelled[:3])), flush=True)
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"{cell.name}.seed{args.seed}.trace{args.trace}.json"
     ).write_text(json.dumps(dict(result=result, modelled=modelled,
                                  tick_s=tick_s)))
    if not done:
        print("bench: no operation completed in the window", file=sys.stderr)
    for d in done:
        if d.error:
            print(f"bench: operation {d.index} failed: {d.error}",
                  file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return result


if __name__ == "__main__":
    sys.exit(main())
