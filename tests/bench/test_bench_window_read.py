"""The readers of the named-bytes FIN read (``window_read_*``) and the byte
counts they rest on: ``bench/roofline.py`` counts the bytes each
``npb_mg_8r`` face names as the program's registry does, and the readers
turn hand-made device events into the numbers their docstrings state."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench import roofline, spec
from bench.run import Run
from bench.trace import Event, Trace

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "npb_mg_8r.comm3_256_loss0"
CONFIG = json.loads((ROOT / "bench" / "configs" / "npb_mg_8r.json")
                    .read_text())


def _reader(name: str):
    return spec.load_metric(ROOT, next(m for m in BENCH["per_layer"]
                                       if m["name"] == name)).read


def test_named_bytes_match_the_registry_at_n130():
    from bench import generator
    from repro import mpi
    reg = mpi.DatatypeRegistry()
    for d in CONFIG["datatypes"]:
        i = reg.register(generator.to_ddt(d["type"]), count=d["count"])
        assert roofline.named_bytes(d["type"], d["count"]) \
            == reg.named(i).size == reg.msg_bytes(i)
    assert roofline.gathered_types(CONFIG["datatypes"]) == [131072, 133120]
    per_call = _reader("window_read_hbm_pct").__globals__[
        "named_bytes_per_call"](CONFIG)
    assert per_call == 132096


def test_config_faces_are_the_reference_faces_at_the_mix_size():
    check = spec.load_check(ROOT, "comm3")
    mix = json.loads((ROOT / "bench" / "traffic" / "comm3_256_loss0.json")
                     .read_text())
    assert check.faces(mix["n"]) == CONFIG["datatypes"]
    assert mix["dims"][0] * mix["dims"][1] * mix["dims"][2] \
        == CONFIG["ranks"]


def _run(modules):
    trace = Trace(Event("window", 0, 10**9), [modules], [[]], [])
    return Run(tick_s=[0.01] * 4, window_s=1.0, setup_s=0.0,
               setup_compile_s=0.0, trace=trace)


def test_window_read_metrics_on_hand_made_events(monkeypatch):
    """Two gathers of 100 us and a NIC step over 4 ticks: 0.05 ms a tick,
    and 2 × 6 × 132,096 B in 200 us against 819 GB/s."""
    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(device_kind="TPU v5 lite")])
    run = _run([Event("jit__read_named(7)", 0, 100_000),
                Event("jit__step_impl(3)", 100_000, 900_000),
                Event("jit__read_named(7)", 900_000, 1_000_000)])
    assert _reader("window_read_device_ms_per_tick")(run) == \
        pytest.approx(0.05)
    assert _reader("window_read_hbm_pct")(run) == pytest.approx(
        100 * 2 * 6 * 132096 / (819e9 * 200e-6))
    idle = _run([Event("jit__step_impl(3)", 0, 900_000)])
    assert _reader("window_read_device_ms_per_tick")(idle) is None
    assert _reader("window_read_hbm_pct")(idle) is None
    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(device_kind="TPU v9 imagined")])
    with pytest.raises(KeyError, match="no HBM peak"):
        _reader("window_read_hbm_pct")(run)
