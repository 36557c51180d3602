"""The comparison that decides ``correct``: the reference's own readings,
the lower-precision control failing every cell's limit, and a CPU run with
the timed path broken underneath coming out not correct."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in BENCH["configs"]}


def _mix(w):
    return json.loads(
        (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())


def _check(mix):
    return spec.load_check(ROOT, mix["op"])


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
    check = _check(mix)
    for seed in range(3):
        inputs = check.draw(mix, config, np.random.default_rng(seed))
        low = check.control(mix, config, inputs)
        for name, value in check.compare(mix, config, inputs, low).items():
            assert value > mix["limits"][name], (name, value)
        exact = check.expected(mix, config, inputs)
        for name, value in check.compare(mix, config, inputs,
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


def _largest_input(workload) -> int:
    """Bytes of the largest array that one operation of the cell draws."""
    mix = _mix(workload)
    inputs = _check(mix).draw(mix, CONFIGS[workload["config"]],
                              np.random.default_rng(0))
    return max(a.nbytes for a in jax.tree_util.tree_leaves(inputs))


# the cells light enough for the CPU to run them whole: no input array of
# an operation reaches 64 KiB
SMALL_CELLS = [w for w in BENCH["workloads"]
               if _largest_input(w) < 64 << 10]


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
