"""Benchmark harness — one module per paper table/figure.

  bench_pingpong : Fig 7  (RTT, 3 modes × ICMP/UDP × payload)
  bench_slmp     : Fig 8  (throughput vs window size, failures)
  bench_fabric   : Fig 8 over the net fabric (loss × window goodput sweep,
                   ping-pong latency vs loss) — also writes the
                   machine-readable ``BENCH_fabric.json``
  bench_mpi      : Fig 10 end-to-end (MPI datatype offload overlap ratio
                   through the lossy fabric, collective goodput vs node
                   count) — writes ``BENCH_mpi.json``
  bench_ddt      : Fig 10 (DDT throughput + overlap ratio, single NIC)
  bench_latency  : Table II (module latencies)
  bench_kernels  : Pallas kernel micro-benchmarks

Usage: ``python -m benchmarks.run [filter] [--filter SCENARIO]`` runs
every suite whose name contains ``filter`` (all when omitted);
``--filter SCENARIO`` additionally restricts suites that define scenarios
(currently ``bench_mpi``) to the scenarios whose name contains SCENARIO
— e.g. ``python -m benchmarks.run mpi --filter allreduce_large`` is the
CI smoke for the large-message fast path.  ``--list`` prints the suite
names.  A filter matching nothing is an error, not a silent no-op.

Suites that write ``BENCH_*.json`` stamp each record with the scenario
name and its harness wall-clock seconds (``harness_seconds``), so a
simulator slowdown is visible across PRs even when modeled ticks stay
flat.

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import inspect
import sys
import time


def main() -> None:
    from benchmarks import (bench_ddt, bench_fabric, bench_kernels,
                            bench_latency, bench_mpi, bench_pingpong,
                            bench_slmp)
    from repro import compile_cache
    compile_cache.configure()
    suites = [
        ("fig7_pingpong", bench_pingpong.run),
        ("fig8_slmp", bench_slmp.run),
        ("fabric", bench_fabric.run),
        ("fig10_ddt", bench_ddt.run),
        ("mpi", bench_mpi.run),
        ("table2_latency", bench_latency.run),
        ("kernels", bench_kernels.run),
    ]
    args = sys.argv[1:]
    scenario = None
    if "--filter" in args:
        i = args.index("--filter")
        assert i + 1 < len(args), "--filter needs a scenario name"
        scenario = args[i + 1]
        args = args[:i] + args[i + 2:]
    only = args[0] if args else None
    if only in ("--list", "-l"):
        for name, _ in suites:
            print(name)
        return
    selected = [(n, fn) for n, fn in suites if not only or only in n]
    if not selected:
        sys.exit(f"no benchmark suite matches {only!r}; available: "
                 + ", ".join(n for n, _ in suites))
    if scenario is not None:
        selected = [(n, fn) for n, fn in selected
                    if "scenario_filter" in inspect.signature(fn).parameters]
        if not selected:
            sys.exit(f"--filter {scenario!r} matches no suite that "
                     f"defines scenarios")
    print("name,us_per_call,derived")
    for name, fn in selected:
        t0 = time.time()
        print(f"# --- {name} ---")
        if "scenario_filter" in inspect.signature(fn).parameters:
            fn(scenario_filter=scenario)
        else:
            fn()
        print(f"# {name} done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
