"""Smoke run of the MPI-over-sPIN fabric on one TPU.

Drives the main path once through the entry points a user calls
(``repro.mpi.Communicator`` -> ``Fabric.tick`` -> ``SpinNIC.step`` and the
link model) at the largest deployment the benchmarks run, in four phases:

  a. a datatype-offloaded receive (the paper's Fig 10 path): 2 ranks, the
     ``complex`` datatype at count 512, loss 0.05;
  b. a 4 MiB-per-rank int64 allreduce on 8 ranks (segmented Rabenseifner,
     ``large_cfg``), loss 0.02;
  c. the trainer's gradient sync (``FabricGradSync``), 4 ranks, 6.1 MiB of
     f32 gradients, loss 0;
  d. each NIC Pallas kernel (matcher, checksum, ddt gather on u8 and f32),
     compiled for the chip and compared with its jnp reference.

Inputs come from fixed seeds and every result is checked exactly against
numpy.  The modelled statistics (ticks, bytes on the wire, messages,
retransmits, link frame counters) are deterministic in the seed, so they
must equal the CPU run of the same code, recorded in ``GOLDEN``; a mismatch
fails the run.

    python chip_smoke.py

Needs a TPU: when JAX's first device is anything else the script exits
non-zero, names the platform it found, and prints no result.  Earlier lines
report per phase the wall seconds, the first tick apart (it compiles), the
compile seconds, modelled ticks and ticks per second.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.bench_mpi import GRAD_SHAPES, fig10_datatypes, large_cfg  # noqa: E402,E501
from repro import compile_cache, mpi  # noqa: E402
from repro.core import ddt as ddtlib  # noqa: E402
from repro.core import matching  # noqa: E402
from repro.core import packet as pkt  # noqa: E402
from repro.kernels.checksum import ops as checksum_ops  # noqa: E402
from repro.kernels.checksum.ref import checksum_ref  # noqa: E402
from repro.kernels.ddt import ops as ddt_ops  # noqa: E402
from repro.kernels.ddt.ref import ddt_gather_ref  # noqa: E402
from repro.kernels.matcher import ops as matcher_ops  # noqa: E402
from repro.kernels.matcher.ref import match_ref  # noqa: E402
from repro.mpi import wire  # noqa: E402
from repro.net import LinkConfig  # noqa: E402
from repro.train.manual_dp import FabricGradSync  # noqa: E402

# Modelled statistics of each phase at full size, from the CPU run.
GOLDEN = {
    "ddt_recv": dict(ticks=54, bytes_wire=61440, msgs_total=1,
                     retransmits=6, frames_pushed=138, frames_lost=6,
                     frames_delivered=132),
    "allreduce": dict(ticks=619, algorithm="allreduce_rab", rounds=6,
                      bytes_wire=58720256, msgs_total=896, retransmits=23951,
                      frames_pushed=127809, frames_lost=2585,
                      frames_delivered=117353),
    "grad_sync": dict(ticks=567, algorithm="allreduce_rab", rounds=4,
                      bytes_wire=39845888, msgs_total=608, retransmits=15062,
                      frames_pushed=80928, frames_lost=0,
                      frames_delivered=71504),
}


class _CompileMeter:
    """Seconds spent in XLA compiles (a persistent-cache hit counts its
    retrieval only) and cache hits, summed from ``jax.monitoring`` events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


METER = _CompileMeter()


def _timed(comm: mpi.Communicator, finish) -> dict:
    """Tick once, timed apart (the first tick compiles), then ``finish()``."""
    s0, h0 = METER.seconds, METER.cache_hits
    t0 = time.perf_counter()
    comm.progress(1)
    t1 = time.perf_counter()
    finish()
    t2 = time.perf_counter()
    return dict(wall_s=t2 - t0, first_tick_s=t1 - t0,
                compile_s=METER.seconds - s0,
                cache_hits=METER.cache_hits - h0)


def _fabric_frames(comm: mpi.Communicator) -> dict:
    links = comm.link_stats()
    return {f"frames_{k}": sum(l[k] for l in links)
            for k in ("pushed", "lost", "delivered")}


def _engine_sum(comm: mpi.Communicator, *keys: str) -> int:
    return sum(e[k] for e in comm.stats() for k in keys)


# ----------------------------------------------------------------- phases
def phase_ddt_recv(loss: float = 0.05) -> dict:
    """One typed irecv/isend pair over 2 ranks; the receiver's NIC unpacks
    the ``complex`` datatype (count 512) into the posted buffer."""
    reg, ids = fig10_datatypes()
    cid = ids["complex"]
    c = reg.committed(cid)
    comm = mpi.Communicator(2, registry=reg, seed=7,
                            link_cfg=LinkConfig(loss=loss, latency=2,
                                                jitter=2))
    rng = np.random.default_rng(0)
    mem = rng.integers(0, 256, c.mem_bytes).astype(np.uint8)
    buf = np.zeros(c.mem_bytes, np.uint8)
    t0 = comm.now
    r = comm.irecv(1, buf, source=0, tag=1)
    s = comm.isend(0, 1, mem, tag=1, datatype=cid)
    timing = _timed(comm, lambda: comm.wait(r, s, max_ticks=200_000))
    expect = ddtlib.unpack_np(c, ddtlib.pack_np(c, mem),
                              np.zeros(c.mem_bytes, np.uint8))
    np.testing.assert_array_equal(buf, expect)
    modelled = dict(ticks=comm.now - t0,
                    bytes_wire=_engine_sum(comm, "bytes_sent"),
                    msgs_total=_engine_sum(comm, "eager_sent", "rdv_sent"),
                    retransmits=_engine_sum(comm, "retransmits"),
                    **_fabric_frames(comm))
    return dict(modelled=modelled, timing=timing)


def phase_allreduce(n_ranks: int = 8, nbytes: int = 4 << 20,
                    loss: float = 0.02) -> dict:
    """``iallreduce(algorithm="auto")`` of int64 vectors, checked exactly."""
    comm = mpi.Communicator(n_ranks, seed=31, cfg=large_cfg(),
                            link_cfg=LinkConfig(loss=loss, latency=1))
    rng = np.random.default_rng(21)
    vals = [rng.integers(0, 1 << 20, nbytes // 8).astype(np.int64)
            for _ in range(n_ranks)]
    ref = np.sum(np.stack(vals), axis=0)
    t0 = comm.now
    h = mpi.iallreduce(comm, vals, algorithm="auto")
    timing = _timed(comm, lambda: comm.wait(h, max_ticks=4_000_000))
    for out in h.result:
        np.testing.assert_array_equal(out, ref)
    modelled = dict(ticks=comm.now - t0, algorithm=h.algorithm,
                    rounds=h.rounds, bytes_wire=h.bytes_wire,
                    msgs_total=h.msgs_total,
                    retransmits=_engine_sum(comm, "retransmits"),
                    **_fabric_frames(comm))
    # where the simulated state lives: one node's NIC host window and the
    # stacked link buffers
    devices = (comm.nodes[0].state.host.devices()
               | comm.fabric._stack.data.devices())
    return dict(modelled=modelled, timing=timing, devices=devices)


def phase_grad_sync(n_ranks: int = 4, shapes: dict = GRAD_SHAPES) -> dict:
    """``FabricGradSync`` mean of one f32 gradient pytree per rank, loss 0,
    checked with the tolerances of the ``grad_allreduce`` benchmark."""
    comm = mpi.Communicator(n_ranks, seed=7, cfg=large_cfg(),
                            link_cfg=LinkConfig(latency=1))
    rng = np.random.default_rng(33)
    grads = [{k: rng.normal(size=s).astype(np.float32)
              for k, s in shapes.items()} for _ in range(n_ranks)]
    ref = {k: np.mean(np.stack([g[k] for g in grads]), axis=0,
                      dtype=np.float64).astype(np.float32) for k in shapes}
    sync = FabricGradSync(comm)
    sync.post(grads)
    means = []
    timing = _timed(comm, lambda: means.extend(sync.wait()))
    for m in means:
        for k in shapes:
            np.testing.assert_allclose(m[k], ref[k], rtol=1e-5, atol=1e-6)
    st = sync.last_stats
    modelled = dict(ticks=st["total_ticks"], algorithm=st["algorithm"],
                    rounds=st["rounds"], bytes_wire=st["bytes_wire"],
                    msgs_total=st["msgs_total"],
                    retransmits=_engine_sum(comm, "retransmits"),
                    **_fabric_frames(comm))
    return dict(modelled=modelled, timing=timing)


def _kernel_frames(rng: np.random.Generator, n: int) -> list:
    """Frames of every kind the NIC's rulesets see, plus unmatched ones."""
    frames = []
    for i in range(n):
        payload = rng.integers(0, 256, int(rng.integers(2, 1024))
                               ).astype(np.uint8)
        kind = i % 5
        if kind == 0:
            frames.append(pkt.make_icmp_echo(payload, seq=i))
        elif kind == 1:
            frames.append(pkt.make_udp(payload, dport=9999))
        elif kind in (2, 3):
            port = wire.EAGER_PORT if kind == 2 else wire.DATA_PORT
            frames.append(pkt.make_slmp(i, 0, pkt.SLMP_FLAG_EOM * (i % 2),
                                        payload, dport=port))
        else:
            frames.append(pkt.make_udp(payload, dport=1234))
    return frames


def _kernel_check(fn, ref, *args) -> bool:
    """Run ``fn`` and compare with ``ref``; True when the lowered program
    holds a Mosaic kernel (compiled, not interpreted)."""
    text = jax.jit(fn).lower(*args).as_text()
    for got, want in zip(jax.tree.leaves(fn(*args)),
                         jax.tree.leaves(ref(*args))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    return "tpu_custom_call" in text


def phase_kernels(batch: int = 32, n_frames: int = 128,
                  count: int = 512) -> dict:
    """Each NIC Pallas kernel once through its ``ops`` wrapper."""
    s0, h0 = METER.seconds, METER.cache_hits
    t0 = time.perf_counter()
    rng = np.random.default_rng(5)
    tables = matching.MatchTables.build([
        matching.ruleset_slmp(wire.EAGER_PORT),
        matching.ruleset_slmp(wire.DATA_PORT),
        matching.ruleset_icmp_echo(),
        matching.ruleset_udp_pingpong(9999)])
    words = pkt.stack_frames(_kernel_frames(rng, batch)).words()
    compiled = dict(matcher=_kernel_check(
        lambda w: matcher_ops.match(w, tables.rules, tables.modes,
                                    use_kernel=True),
        lambda w: match_ref(w, tables.rules, tables.modes), words))

    icmp = [pkt.make_icmp_echo(rng.integers(0, 256, int(rng.integers(
        0, pkt.MTU - 42))).astype(np.uint8), seq=i) for i in range(n_frames)]
    frames = pkt.stack_frames(icmp)
    compiled["checksum"] = _kernel_check(
        lambda d, n: checksum_ops.internet_checksum(
            d, n, start=pkt.L4_BASE, use_kernel=True),
        lambda d, n: checksum_ref(d, n, pkt.L4_BASE),
        frames.data, frames.length)
    # every frame carries a correct ICMP checksum: the total folds to zero
    np.testing.assert_array_equal(
        np.asarray(checksum_ops.internet_checksum(
            frames.data, frames.length, start=pkt.L4_BASE,
            use_kernel=True)), 0)

    c = ddtlib.commit(ddtlib.complex_ddt(), count=count)
    msg_u8 = jnp.asarray(rng.integers(0, 256, c.msg_bytes).astype(np.uint8))
    _, unpack_idx = ddtlib.element_maps(c, 4)
    msg_f32 = jnp.asarray(rng.normal(size=c.msg_bytes // 4)
                          .astype(np.float32))
    for name, src, idx in (("ddt_u8", msg_u8, c.mem_to_msg),
                           ("ddt_f32", msg_f32, unpack_idx)):
        compiled[name] = _kernel_check(
            lambda s, i: ddt_ops.gather(s, i, use_kernel=True),
            ddt_gather_ref, src, jnp.asarray(idx))
    timing = dict(wall_s=time.perf_counter() - t0,
                  compile_s=METER.seconds - s0,
                  cache_hits=METER.cache_hits - h0)
    return dict(modelled={}, timing=timing, compiled=compiled)


PHASES = (("ddt_recv", phase_ddt_recv), ("allreduce", phase_allreduce),
          ("grad_sync", phase_grad_sync), ("kernels", phase_kernels))


def _report(name: str, res: dict) -> None:
    t, m = res["timing"], res["modelled"]
    line = {"phase": name, **{k: round(v, 3) if isinstance(v, float) else v
                              for k, v in t.items()}}
    if "ticks" in m:
        line["ticks_per_s_ex_compile"] = round(
            m["ticks"] / max(t["wall_s"] - t["compile_s"], 1e-9), 1)
    line.update(m)
    if "compiled" in res:
        line["compiled"] = res["compiled"]
    print(json.dumps(line), flush=True)


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's first device is on "
                 f"platform {dev.platform!r} ({dev.device_kind})")
    cache_dir = compile_cache.configure()
    jax.monitoring.register_event_duration_secs_listener(METER.on_duration)
    jax.monitoring.register_event_listener(METER.on_event)
    count = len(jax.devices())
    print(json.dumps(dict(jax=jax.__version__, platform=dev.platform,
                          device_kind=dev.device_kind, device_count=count,
                          compile_cache=cache_dir)), flush=True)
    for name, fn in PHASES:
        res = fn()
        _report(name, res)
        if name in GOLDEN:
            assert res["modelled"] == GOLDEN[name], (
                f"{name}: modelled statistics differ from the CPU run: "
                f"got {res['modelled']}, want {GOLDEN[name]}")
        if "devices" in res:
            assert res["devices"] == {dev}, (
                f"{name}: simulated state lives on {res['devices']}, "
                f"not on {dev}")
        if "compiled" in res:
            assert all(res["compiled"].values()), (
                f"{name}: kernels not compiled for the chip: "
                f"{res['compiled']}")
    print(json.dumps(dict(ok=True, device=dict(
        platform=dev.platform, kind=dev.device_kind, count=count))))


if __name__ == "__main__":
    main()
