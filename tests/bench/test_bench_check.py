"""The comparison that decides ``correct``: the reference's own readings,
the lower-precision control failing every cell's limit, and a CPU run with
the timed path broken underneath coming out not correct."""
from __future__ import annotations

import collections
import contextlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec
from tests.bench.test_bench_traffic import _shrink

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


HOST_WINDOW = "d2h.host_window"


def _corrupt_host_window(monkeypatch, corrupt, fired) -> None:
    """Every device-to-host conversion of an array made while the
    program's innermost span is ``d2h.host_window`` returns its bytes
    after ``corrupt``: whichever method reads the window, and whether by
    ``np.asarray`` (the buffer protocol on the CPU) or ``jax.device_get``
    (the array's ``_value``).  ``fired()`` counts each one."""
    from repro import obs

    active = []
    span = obs.span

    @contextlib.contextmanager
    def tracked(name):
        active.append(name)
        try:
            with span(name):
                yield
        finally:
            active.pop()

    array = type(jnp.zeros(0))
    value, buffer = array._value, array.__buffer__

    def in_window() -> bool:
        return bool(active) and active[-1] == HOST_WINDOW

    def host(self):
        out = value.fget(self)
        if in_window() and out.size:
            out = np.array(out)
            corrupt(out.reshape(-1).view(np.uint8))
            fired()
        return out

    monkeypatch.setattr(obs, "span", tracked)
    monkeypatch.setattr(array, "_value", property(host))
    monkeypatch.setattr(array, "__buffer__", lambda self, flags: (
        memoryview(host(self)) if in_window() else buffer(self, flags)))


def _half(b) -> None:
    b[b.size // 2:] = 0


def _flip(b) -> None:
    # the sign of the first float32 the read returns
    b[min(3, b.size - 1)] ^= 0x80


@pytest.mark.parametrize("corrupt", (_half, _flip), ids=("half", "flip"))
def test_read_faults_break_host_window_reads_alone(monkeypatch, corrupt):
    """Inside ``d2h.host_window`` both ways to the host are corrupted and
    counted; the same reads under another span or none are not."""
    from repro import obs
    fired = collections.Counter()
    _corrupt_host_window(monkeypatch, corrupt,
                         lambda: fired.update(("read",)))
    x = jnp.arange(16, dtype=jnp.uint8) + 1
    want = np.arange(16, dtype=np.uint8) + 1
    bad = want.copy()
    corrupt(bad)
    with obs.span("d2h.to_host"):
        assert (np.asarray(x) == want).all()
        assert (jax.device_get(x) == want).all()
    assert (np.asarray(x) == want).all() and not fired
    with obs.span(HOST_WINDOW):
        assert (np.asarray(x) == bad).all()
        assert (jax.device_get(x + 0) == bad).all()
    assert fired["read"] == 2
    assert (np.asarray(x) == want).all()


def _fault(monkeypatch, kind: str, fired: collections.Counter) -> None:
    """Breaks the timed path underneath the harness; ``fired[kind]`` counts
    how often the fault fired."""
    from repro.core import packet as pkt
    from repro.core.spin_nic import SpinNIC
    from repro.net import Node

    def fire(n: int = 1) -> None:
        fired[kind] += n

    if kind == "state_unchanged":
        def step(self, state, batch):
            fire()
            empty = pkt.PacketBatch(batch.data, batch.length,
                                    jnp.zeros_like(batch.valid))
            return state, empty, empty
        monkeypatch.setattr(SpinNIC, "step", step)
    elif kind == "half_left_out":
        _corrupt_host_window(monkeypatch, _half, fire)
    elif kind == "exchange_left_out":
        tick, tick_idle = Node.tick, Node.tick_idle

        def drop(frames):
            fire(bool(frames))
            return []
        monkeypatch.setattr(Node, "tick", lambda self, ingress, now: drop(
            tick(self, ingress, now)))
        monkeypatch.setattr(Node, "tick_idle", lambda self, now: drop(
            tick_idle(self, now)))
    elif kind == "answer_altered":
        _corrupt_host_window(monkeypatch, _flip, fire)


FAULTS = ("state_unchanged", "half_left_out", "exchange_left_out",
          "answer_altered")


def _largest_input(workload) -> int:
    """Bytes of the largest array that one operation of the cell draws."""
    mix = _mix(workload)
    inputs = _check(mix).draw(mix, CONFIGS[workload["config"]],
                              np.random.default_rng(0))
    return max(a.nbytes for a in jax.tree_util.tree_leaves(inputs))


# the cells light enough for the CPU to run them whole: no input array of
# an operation reaches 64 KiB; the others run at their ``small`` size
SMALL_CELLS = [w["name"] for w in BENCH["workloads"]
               if _largest_input(w) < 64 << 10]


def _broken(monkeypatch, fault: str) -> collections.Counter:
    """Puts ``fault`` under the harness's window; the counter says how
    often it fired there."""
    from bench import run
    measure = run.measure
    fired = collections.Counter()

    def broken(traffic, seconds):
        with monkeypatch.context() as m:
            _fault(m, fault, fired)
            return measure(traffic, seconds)
    monkeypatch.setattr(run, "measure", broken)
    return fired


@pytest.mark.parametrize("workload", BENCH["workloads"],
                         ids=lambda w: w["name"])
@pytest.mark.parametrize("fault", FAULTS)
def test_broken_timed_path_is_not_correct(bench_root, run_cpu, monkeypatch,
                                          workload, fault):
    if workload["name"] not in SMALL_CELLS:
        _shrink(bench_root, workload)
    fired = _broken(monkeypatch, fault)
    result, _, err = run_cpu(bench_root, workload["name"], seconds=1.5)
    assert fired[fault] > 0, f"{fault} never fired: its seam was bypassed"
    assert result["correct"] is False, (fault, result)
    assert "compared " in err.strip().splitlines()[-1]


class _GatherReads:
    """A node as its MPI engine sees it, with the engine's host-window
    reads moved to ``gather_window``: a device gather of the bytes, read
    back with ``jax.device_get`` under the same ``d2h.host_window`` span.
    Everything else is the node's own."""

    def __init__(self, node):
        self._node = node

    def __getattr__(self, name):
        return getattr(self._node, name)

    def read_host(self, base, nbytes):
        return self.gather_window(base, nbytes)

    def gather_window(self, base, nbytes):
        from repro import obs
        with obs.span(HOST_WINDOW):
            return jax.device_get(jnp.take(self._node.state.host,
                                           jnp.arange(base, base + nbytes)))


@pytest.mark.parametrize("fault", ("none", "half_left_out", "answer_altered"))
def test_renamed_host_window_read_is_still_broken(bench_root, run_cpu,
                                                  monkeypatch, fault):
    """With the DDT cell's host-window reads going around ``Node.read_host``
    and ``SpinNIC.read_host``: sound, the run is correct; under either read
    fault it is not, since the faults are tied to the span."""
    from repro.core.spin_nic import SpinNIC
    from repro.mpi.engine import MpiHostEngine
    from repro.net import Node

    def bypassed(*_):
        raise AssertionError("the read went through the old method")
    attach = MpiHostEngine.attach
    monkeypatch.setattr(MpiHostEngine, "attach",
                        lambda self, node: attach(self, _GatherReads(node)))
    monkeypatch.setattr(Node, "read_host", bypassed)
    monkeypatch.setattr(SpinNIC, "read_host", bypassed)
    fired = _broken(monkeypatch, fault) if fault != "none" else None
    result, _, err = run_cpu(bench_root, "ddt_fig10_2r.complex_loss5",
                             seconds=1.5)
    assert result["attempted"] >= 1
    if fired is None:
        assert result["correct"] is True, err
    else:
        assert fired[fault] > 0, f"{fault} never fired on the renamed read"
        assert result["correct"] is False, (fault, result)
