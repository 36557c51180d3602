"""The program's span catalogue (``repro.obs``) on the fabric tick: traced
CPU windows of the harness show every site the path runs, name every
device-to-host read inside a tick, and give the span metrics numbers."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from bench import spans as bench_spans
from bench import spec
from bench.run import Run
from bench.trace import Trace
from repro import obs
from tests.bench.conftest import bench_root, run_cpu  # noqa: F401
from tests.bench.test_bench_traffic import _shrink

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SPAN_METRICS = [m for m in BENCH["per_layer"]
                if m["source"] == "program_span"]
READ = "np.asarray(jax.Array)"


def test_catalogue_is_the_one_the_readers_match():
    assert len(set(obs.SPANS)) == len(obs.SPANS) == 16
    assert set(obs.SPANS) == bench_spans.PROGRAM_SPANS
    assert all(re.fullmatch(r"[a-z0-9]+\.[a-z_]+", n) for n in obs.SPANS)


def test_every_span_in_the_program_is_catalogued_and_placed():
    used = set()
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        used |= set(re.findall(r'obs\.span\("([^"]+)"\)', path.read_text()))
    # ``d2h.egress`` is read under ``d2h.to_host`` now; it stays in the
    # catalogue only because the trace readers (bench/spans.py) match it
    assert used == set(obs.SPANS) - {"d2h.egress"}
    with pytest.raises(ValueError, match="catalogue"):
        obs.span("nic.stepp")


def _within(ev, outer) -> int:
    """How many of the events ``outer`` hold ``ev`` whole."""
    return sum(o.start_ns <= ev.start_ns and ev.end_ns <= o.end_ns
               for o in outer)


# A 2-rank typed receive (rendezvous into a committed datatype), and a
# 4-rank 128 KiB allreduce: segmented Rabenseifner over the credit-managed
# rendezvous, so engines read the DMA window, arm the expect table and
# step a collective plan.  No traced tick reads egress or the completion
# FIFO on its own: a NIC step's outputs come back in one ``d2h.to_host``.
CASES = {"ddt_fig10_2r.complex_loss5": {"mpi.plan", "d2h.egress",
                                        "d2h.completions"},
         "allreduce_8r.1MiB_loss2": {"d2h.egress", "d2h.completions"}}


@pytest.mark.parametrize("workload", list(CASES))
def test_traced_window_names_every_site_and_read(bench_root, run_cpu,
                                                 workload):
    _shrink(bench_root, next(w for w in BENCH["workloads"]
                             if w["name"] == workload))
    result, _, _ = run_cpu(bench_root, workload, seconds=2, trace=1)
    assert result["correct"] and result["attempted"] >= 1

    trace = Trace.from_dir(bench_root / ".bench_out" / "trace")
    spans = bench_spans.program_spans(trace)
    assert {ev.name for ev in spans} == set(obs.SPANS) - CASES[workload]

    # every device-to-host read inside a tick is named by one d2h span
    ticks = [ev for ev in trace.host if ev.name == "tick"]
    d2h = [ev for ev in spans if ev.name in bench_spans.D2H]
    reads = [ev for ev in trace.host if ev.name == READ and _within(ev, ticks)]
    assert reads
    assert all(_within(r, d2h) == 1 for r in reads)
    # a read waits for the device and does nothing else
    engines = [ev for ev in spans if ev.name in bench_spans.ENGINE]
    assert not any(_within(e, d2h) for e in engines)

    # every span metric reads a number on this window, and the harness
    # reports each in the cells that list it
    ticks_s = [(ev.end_ns - ev.start_ns) / 1e9 for ev in ticks]
    run = Run(tick_s=ticks_s, window_s=trace.window_s, setup_s=0.0,
              setup_compile_s=0.0, trace=trace)
    got = {m["name"]: spec.load_metric(ROOT, m).read(run)
           for m in SPAN_METRICS}
    assert all(v is not None and v >= 0 for v in got.values())
    assert got["d2h_syncs_per_tick"] >= 1
    assert got["engine_ms_per_tick"] > 0
    applies = {m["name"] for m in SPAN_METRICS
               if workload in m["workloads"]}
    assert all(result["metrics"][name]["value"] >= 0 for name in applies)
