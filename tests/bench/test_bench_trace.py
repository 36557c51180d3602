"""The reduction from a profiler trace to per-layer metrics, on a trace of
the ``ddt_fig10_2r.complex_loss5`` cell recorded on one TPU v5e (a 1 s
traced window, 88 fabric ticks; see the fixture's ``recorded`` key)."""
from __future__ import annotations

import json
import lzma
import math
import re
from pathlib import Path

import jax
import pytest

from bench import spec
from bench.run import Run
from bench.trace import Trace, program_name

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
NAME = "ddt_fig10_2r.complex_loss5.trace1"


@pytest.fixture(scope="module")
def recorded():
    raw = lzma.decompress((DATA / f"{NAME}.xplane.pb.xz").read_bytes())
    meta = json.loads((DATA / f"{NAME}.json").read_text())
    return raw, meta


@pytest.fixture(scope="module")
def run(recorded):
    raw, meta = recorded
    return Run(tick_s=meta["tick_s"], window_s=meta["window_s"],
               setup_s=0.0, setup_compile_s=meta["setup_compile_s"],
               trace=Trace.from_bytes(raw))


def test_per_layer_metrics_read_the_recorded_numbers(run, recorded):
    _, meta = recorded
    cell = spec.load(ROOT, "ddt_fig10_2r.complex_loss5")
    got = {m.name: m.read(run) for m in cell.per_layer}
    # what the recording read; a metric listed for the cell since then
    # reads a number here, or nothing (the program spans came later)
    recorded = {name: got.pop(name) for name in meta["metrics"]}
    assert recorded == pytest.approx(meta["metrics"], rel=1e-12)
    assert all(v is None or math.isfinite(v) for v in got.values())
    assert recorded["device_calls_per_tick"] == 638 / 88
    assert 0 < recorded["device_idle_pct"] < 100


def test_window_busy_and_breakdown(run, recorded):
    _, meta = recorded
    t = run.trace
    assert t.window_s == pytest.approx(meta["window_s"], abs=1e-6)
    assert t.busy_s() == pytest.approx(meta["busy_s"], rel=1e-12)
    assert t.breakdown() == meta["breakdown"]
    ops = t.breakdown()["device_ops"]
    assert ops[0][0] == "jit__step_impl" and len(ops) <= 10
    gaps = [g for _, g in t.breakdown()["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) == 10


def test_reduction_agrees_with_a_plain_count(recorded):
    """Module executions and busy time, counted straight from the
    profiler's planes, agree with the reduction."""
    raw, _ = recorded
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    t = Trace.from_bytes(raw)
    lo, hi = t.window.start_ns, t.window.end_ns
    dev = pd.find_plane_with_name("/device:TPU:0")
    lines = {ln.name: ln for ln in dev.lines}
    mods = [e for e in lines["XLA Modules"].events
            if lo <= e.start_ns < hi]
    assert len(mods) == sum(len(m) for m in t.modules) == 638
    steps = [e for e in mods if re.match(r"jit__step_impl\(\d+\)$", e.name)]
    assert sum(e.duration_ns for e in steps) / 1e9 == pytest.approx(
        t.module_seconds()["jit__step_impl"], rel=1e-9)
    # busy: sweep the op intervals on a 1 us grid
    busy_us = set()
    for e in lines["XLA Ops"].events:
        s, f = max(e.start_ns, lo), min(e.end_ns, hi)
        busy_us.update(range(int(s // 1000), int(-(-f // 1000))))
    assert t.busy_s() == pytest.approx(len(busy_us) / 1e6, rel=0.05)


def test_program_name_drops_the_compilation_id():
    assert program_name("jit__pop_all(1234567)") == "jit__pop_all"
    assert program_name("jit_scatter") == "jit_scatter"
