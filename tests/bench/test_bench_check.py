"""The comparison that decides ``correct``: the reference's own readings,
the lower-precision control failing every cell's limit, and a CPU run with
the timed path broken underneath coming out not correct."""
from __future__ import annotations

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in BENCH["configs"]}


def _mix(w):
    return json.loads(
        (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())


def _inputs(mix, config, rng):
    if mix["op"] == "allreduce":
        n = mix["bytes_per_rank"] // 4
        return [rng.standard_normal(n).astype(np.float32)
                for _ in range(config["ranks"])]
    dt = next(d for d in config["datatypes"] if d["name"] == mix["datatype"])
    _, span = reference.typed_layout(dt["type"], dt["count"])
    return rng.standard_normal(span // 4).astype(np.float32).view(np.uint8)


def test_fig9_complex_typemap():
    """Fig. 9 ``complex``: 30 floats a instance, 120 bytes on the wire over
    a 92 byte extent; at count 512 a 61,440 byte message."""
    cfg = CONFIGS["ddt_fig10_2r"]
    dt = next(d for d in cfg["datatypes"] if d["name"] == "complex")
    one, extent = reference.typemap(dt["type"])
    assert (len(one), extent) == (120, 92)
    assert one[:12] == list(range(12)) and one[12:16] == [16, 17, 18, 19]
    offs, span = reference.typed_layout(dt["type"], dt["count"])
    assert (offs.size, span) == (61440, 47104)


@pytest.mark.parametrize("workload", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_control_fails_and_reference_passes(workload):
    mix = _mix(workload)
    config = CONFIGS[workload["config"]]
    for seed in range(3):
        inputs = _inputs(mix, config, np.random.default_rng(seed))
        low = reference.control_outputs(mix, config, inputs)
        for name, value in reference.compare(mix, config, inputs,
                                             low).items():
            assert value > mix["limits"][name], (name, value)
        if mix["op"] == "allreduce":
            exact = [np.sum(np.stack(inputs).astype(np.float64), axis=0)
                     .astype(np.float32)] * len(inputs)
        else:
            dt = next(d for d in config["datatypes"]
                      if d["name"] == mix["datatype"])
            exact = reference.expected_typed_recv(dt["type"], dt["count"],
                                                  inputs)
        for name, value in reference.compare(mix, config, inputs,
                                             exact).items():
            assert value <= mix["limits"][name], (name, value)


def _fault(monkeypatch, kind: str) -> None:
    """Breaks the timed path underneath the harness."""
    from repro.core import packet as pkt
    from repro.core.spin_nic import SpinNIC
    from repro.net import Node

    read_host = Node.read_host
    if kind == "state_unchanged":
        def step(self, state, batch):
            empty = pkt.PacketBatch(batch.data, batch.length,
                                    jnp.zeros_like(batch.valid))
            return state, empty, empty
        monkeypatch.setattr(SpinNIC, "step", step)
    elif kind == "half_left_out":
        def half(self, base, nbytes):
            out = np.array(read_host(self, base, nbytes))
            out[out.size // 2:] = 0
            return out
        monkeypatch.setattr(Node, "read_host", half)
    elif kind == "exchange_left_out":
        tick = Node.tick
        monkeypatch.setattr(Node, "tick",
                            lambda self, ingress, now: (tick(self, ingress,
                                                             now), [])[1])
        monkeypatch.setattr(Node, "tick_idle", lambda self, now: [])
    elif kind == "answer_altered":
        def flip(self, base, nbytes):
            # the sign of the first float32 the read returns
            out = np.array(read_host(self, base, nbytes))
            out[min(3, out.size - 1)] ^= 0x80
            return out
        monkeypatch.setattr(Node, "read_host", flip)


FAULTS = ("state_unchanged", "half_left_out", "exchange_left_out",
          "answer_altered")
SMALL_CELLS = [w for w in BENCH["workloads"]
               if _mix(w).get("bytes_per_rank", 0) < 64 << 10]


@pytest.mark.parametrize("workload", SMALL_CELLS, ids=lambda w: w["name"])
@pytest.mark.parametrize("fault", FAULTS)
def test_broken_timed_path_is_not_correct(bench_root, run_cpu, monkeypatch,
                                          workload, fault):
    from bench import run
    measure = run.measure

    def broken(traffic, seconds):
        with monkeypatch.context() as m:
            _fault(m, fault)
            return measure(traffic, seconds)
    monkeypatch.setattr(run, "measure", broken)
    result, _, err = run_cpu(bench_root, workload["name"], seconds=1.5)
    assert result["correct"] is False, (fault, result)
    assert "compared " in err.strip().splitlines()[-1]
