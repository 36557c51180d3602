"""The readers of the program's own spans (``bench/spans.py`` and the six
``*_per_tick`` metrics), on a trace of the ``ddt_fig10_2r.complex_loss5``
cell recorded on one TPU v5e by a build with the spans in place (a 1 s
traced window; see the fixture's ``recorded`` key), and on the earlier
recording by a build without them, and on hand-made events.  The readers
are loaded straight from their ``BENCHMARK.json`` entries."""
from __future__ import annotations

import json
import lzma
from pathlib import Path

import pytest

from bench import spans
from bench import spec
from bench.run import Run
from bench.trace import Event, Trace

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "ddt_fig10_2r.complex_loss5"
SPAN_METRICS = [m for m in json.loads((ROOT / "BENCHMARK.json").read_text())[
    "per_layer"] if m["source"] == "program_span"]


def _load(name: str) -> Run:
    meta = json.loads((DATA / f"{name}.json").read_text())
    raw = lzma.decompress((DATA / f"{name}.xplane.pb.xz").read_bytes())
    return Run(tick_s=meta["tick_s"], window_s=meta["window_s"],
               setup_s=0.0, setup_compile_s=meta["setup_compile_s"],
               trace=Trace.from_bytes(raw)), meta


@pytest.fixture(scope="module")
def recorded():
    return _load(f"{CELL}.trace1.spans")


def _readers():
    return {m["name"]: spec.load_metric(ROOT, m) for m in SPAN_METRICS}


def test_span_metrics_read_the_recorded_numbers(recorded):
    run, meta = recorded
    got = {name: m.read(run) for name, m in _readers().items()}
    assert len(got) == 6
    # the DDT receive runs no collective plan: spans, but none of its own
    assert got.pop("plan_ms_per_tick") == 0.0
    want = {name: meta["metrics"][name] for name in got}
    assert got == pytest.approx(want, rel=1e-12)
    assert all(v > 0 for v in got.values())


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return sum(e - s for s, e in out)


def test_self_seconds_agrees_with_a_plain_sweep(recorded):
    """Each span less the union of the program spans that lie inside it."""
    run, _ = recorded
    evs = spans.program_spans(run.trace)
    assert len(evs) > 100
    want = {}
    for i, a in enumerate(evs):
        # in order of start, an enclosing span first
        inner = [(b.start_ns, b.end_ns) for b in evs[i + 1:]
                 if b.start_ns < a.end_ns and b.end_ns <= a.end_ns]
        want[a.name] = want.get(a.name, 0.0) + (
            a.end_ns - a.start_ns - _union(inner)) / 1e9
    got = spans.self_seconds(run.trace)
    assert got == pytest.approx(want, rel=1e-9, abs=1e-12)
    assert set(got) <= spans.PROGRAM_SPANS


def test_idle_by_span_sums_to_the_idle_time(recorded):
    run, _ = recorded
    idle = spans.idle_by_span(run.trace)
    total = sum(e - s for s, e in run.trace.idle_gaps()) / 1e9
    assert sum(idle.values()) == pytest.approx(total, rel=1e-9)
    assert set(idle) <= spans.PROGRAM_SPANS | {spans.NONE}
    assert idle[spans.NONE] < total


def test_span_metrics_read_none_without_program_spans():
    run, _ = _load(f"{CELL}.trace1")
    assert not spans.program_spans(run.trace)
    assert {name: m.read(run) for name, m in _readers().items()} == \
        dict.fromkeys(m["name"] for m in SPAN_METRICS)


def test_every_read_of_a_recorded_tick_is_named(recorded):
    """On the chip, each runtime device-to-host read inside a tick lies in
    exactly one ``d2h.*`` span, and no runtime event of a tick lies
    outside every program span."""
    run, _ = recorded
    evs = spans.program_spans(run.trace)
    d2h = [ev for ev in evs if ev.name in spans.D2H]
    ticks = [ev for ev in run.trace.host if ev.name == "tick"]

    def inside(ev, outer):
        return sum(o.start_ns <= ev.start_ns and ev.end_ns <= o.end_ns
                   for o in outer)

    in_ticks = [ev for ev in run.trace.host if ev.name != "tick"
                and ev.name not in spans.PROGRAM_SPANS and inside(ev, ticks)]
    reads = [ev for ev in in_ticks if ev.name == "np.asarray(jax.Array)"]
    assert len(reads) > 5 * len(ticks)
    assert all(inside(ev, d2h) == 1 for ev in reads)
    assert all(inside(ev, evs) for ev in in_ticks)


def test_a_new_span_of_a_known_layer_is_read():
    """A site the program adds, ``d2h.new_read``, is a program span by its
    layer alone: counted and timed as a read, and taken out of its
    parent's self time; the harness's ``tick`` and the runtime's
    ``np.asarray(jax.Array)`` are not program spans."""
    assert "d2h.new_read" not in spans.PROGRAM_SPANS
    host = [Event("tick", 0, 1000), Event("nic.step", 100, 600),
            Event("d2h.new_read", 200, 450),
            Event("np.asarray(jax.Array)", 210, 440),
            Event("PjitFunction(_step_impl)", 110, 190),
            Event("engine.poll", 700, 900), Event("window", 0, 1000)]
    trace = Trace(Event("window", 0, 1000), [[]], [[]], host)
    run = Run(tick_s=[1e-6], window_s=1e-6, setup_s=0.0,
              setup_compile_s=0.0, trace=trace)
    assert [ev.name for ev in spans.program_spans(trace)] == [
        "nic.step", "d2h.new_read", "engine.poll"]
    assert spans.self_seconds(trace) == pytest.approx({
        "nic.step": 250e-9, "d2h.new_read": 250e-9, "engine.poll": 200e-9})
    got = {name: m.read(run) for name, m in _readers().items()}
    assert got == pytest.approx({
        "d2h_syncs_per_tick": 1.0, "d2h_ms_per_tick": 250e-6,
        "dispatch_ms_per_tick": 250e-6, "engine_ms_per_tick": 200e-6,
        "plan_ms_per_tick": 0.0, "fabric_host_ms_per_tick": 0.0})
    assert spans.idle_by_span(trace) == pytest.approx({
        "none": 300e-9, "nic.step": 250e-9, "d2h.new_read": 250e-9,
        "engine.poll": 200e-9})
