"""The readers of the program's own spans (``bench/spans.py`` and the six
``*_per_tick`` metrics), on a trace of the ``ddt_fig10_2r.complex_loss5``
cell recorded on one TPU v5e by a build with the spans in place (a 1 s
traced window; see the fixture's ``recorded`` key), and on the earlier
recording by a build without them.  The readers are loaded straight from
their ``BENCHMARK.json`` entries: the cells that list them are the two
``allreduce_8r`` ones, and this fixture is of the DDT cell."""
from __future__ import annotations

import json
import lzma
from pathlib import Path

import pytest

from bench import spans
from bench import spec
from bench.run import Run
from bench.trace import Trace

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
    want = dict.fromkeys(spans.PROGRAM_SPANS, 0.0)
    for i, a in enumerate(evs):
        # in order of start, an enclosing span first
        inner = [(b.start_ns, b.end_ns) for b in evs[i + 1:]
                 if b.start_ns < a.end_ns and b.end_ns <= a.end_ns]
        want[a.name] += (a.end_ns - a.start_ns - _union(inner)) / 1e9
    got = spans.self_seconds(run.trace, spans.PROGRAM_SPANS)
    assert got == pytest.approx(want, rel=1e-9, abs=1e-12)


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
