"""Paper Fig 10 *end-to-end*: MPI datatype receive offload measured through
the lossy multi-node fabric, plus collective goodput vs node count.

Overlap methodology (paper §V-C): the receiver posts ``irecv`` for a typed
message, then runs a host computation sized — as in the paper — "slightly
longer than the data transfer" (1.25× the calibrated lossless transfer
time), then polls for completion.  The NIC unpacks every payload byte
through the committed index map while the host computes, so

    R = T_MM / (T_MM + T_Poll),   T_Poll = max(0, T_xfer − T_MM)

Times are *modeled* fabric ticks mapped to wall time via the same
``TICK_NS`` calibration bench_fabric uses (4-tick RTT = 30 us), so the
numbers live in the paper's 100G setting, not this host's speed.  At
loss=0 the transfer hides completely (R ≈ 1); loss makes retransmission
tails poke out of the compute window — the curve the paper cannot show.

A host-unpack baseline row (the same gather run with numpy on the host
after a raw transfer) quantifies what the offload removes from T_Poll.

Writes every point to ``BENCH_mpi.json``.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import List, Optional

import numpy as np

from benchmarks.common import row, timed_scenario
from repro import mpi
from repro.core import ddt as ddtlib
from repro.net import LinkConfig

LOSSES = [0.0, 0.02, 0.05]
ITERS = 4
TICK_NS = 7_500.0                       # 4-tick RTT == 30 us (bench_fabric)
MM_FACTOR = 1.25                        # compute = 1.25 x lossless transfer
NODE_COUNTS = [2, 4, 8]
COLLECTIVE_BYTES = 1 << 13              # per-rank payload for goodput rows
JSON_PATH = "BENCH_mpi.json"

# ---- large-message fast path sweep (per-rank vector sizes) ----
LARGE_SIZES = [256 << 10, 1 << 20, 4 << 20]
LARGE_LOSSES = [0.0, 0.02]
LARGE_RANKS = 8

# a transformer-block-shaped gradient pytree, ~6.1 MiB of f32
GRAD_SHAPES = dict(wq=(1024, 256), wk=(1024, 256), wv=(1024, 256),
                   wo=(256, 1024), w_up=(256, 1024), w_down=(1024, 192),
                   embed=(4096, 24), norm=(1024,))


def large_cfg() -> mpi.MpiConfig:
    """Wire sized for multi-MiB vectors: big frames, deep SLMP windows,
    wide NIC ingress batches, 128 KiB rendezvous segments, 8 slot
    credits — the configuration the gradient-sync numbers are quoted at."""
    return mpi.MpiConfig(batch=32, slmp_window=64, mtu_payload=1408,
                         n_rdv_slots=8, coll_seg_bytes=128 << 10)


def fig10_datatypes():
    reg = mpi.DatatypeRegistry()
    return reg, dict(
        simple=reg.register(ddtlib.simple_ddt(), count=1024, name="simple"),
        complex=reg.register(ddtlib.complex_ddt(), count=512,
                             name="complex"),
    )


def _one_transfer(comm: mpi.Communicator, cid: int, mem, buf,
                  max_ticks=200_000) -> int:
    """Ticks from posting irecv+isend to receive completion."""
    t0 = comm.now
    r = comm.irecv(1, buf, source=0, tag=1)
    s = comm.isend(0, 1, mem, tag=1, datatype=cid)
    comm.wait(r, s, max_ticks=max_ticks)
    return comm.now - t0


def _overlap_sweep(records: List[dict]) -> None:
    reg, ids = fig10_datatypes()
    comm = mpi.Communicator(2, registry=reg, seed=0)
    rng = np.random.default_rng(0)
    for name, cid in ids.items():
        c = reg.committed(cid)
        mem = rng.integers(0, 256, c.mem_bytes).astype(np.uint8)
        buf = np.zeros(c.mem_bytes, np.uint8)
        # calibrate T_MM on a lossless wire (the paper sizes its matmul
        # against the undisturbed transfer)
        comm.rewire(link_cfg=LinkConfig(loss=0.0, latency=2, jitter=2),
                    seed=1)
        t_xfer0 = _one_transfer(comm, cid, mem, buf)
        t_mm = int(np.ceil(MM_FACTOR * t_xfer0))
        # host-unpack baseline: what T_Poll would additionally carry if the
        # host ran the gather (numpy dataloop) instead of the NIC
        msg = ddtlib.pack_np(c, mem)
        host_dst = np.zeros(c.mem_bytes, np.uint8)
        t0 = time.perf_counter()
        for _ in range(5):
            ddtlib.unpack_np(c, msg, host_dst)
        host_unpack_us = (time.perf_counter() - t0) / 5 * 1e6
        for loss in LOSSES:
            comm.rewire(link_cfg=LinkConfig(loss=loss, latency=2, jitter=2),
                        seed=7)
            ratios, xfers, retx = [], [], 0
            for it in range(ITERS):
                buf[:] = 0
                t_xfer = _one_transfer(comm, cid, mem, buf)
                t_poll = max(0, t_xfer - t_mm)
                ratios.append(t_mm / (t_mm + t_poll))
                xfers.append(t_xfer)
            retx = comm.stats()[0]["retransmits"]
            r_mean = float(np.mean(ratios))
            gbps = c.msg_bytes * 8 / (np.mean(xfers) * TICK_NS)
            rec = dict(kind="mpi_overlap", datatype=name, loss=loss,
                       msg_bytes=c.msg_bytes, mem_bytes=c.mem_bytes,
                       t_mm_ticks=t_mm, t_xfer_ticks=float(np.mean(xfers)),
                       overlap_ratio=round(r_mean, 4),
                       goodput_gbps=round(float(gbps), 3),
                       retransmits=retx,
                       host_unpack_us=round(host_unpack_us, 1))
            records.append(rec)
            row(f"mpi_overlap_{name}_loss{int(loss * 100)}",
                np.mean(xfers) * TICK_NS / 1e3,
                f"R={r_mean:.4f};gbps={gbps:.2f};retx={retx};"
                f"host_unpack_us={host_unpack_us:.0f}")


def _collective_sweep(records: List[dict]) -> None:
    """Collective goodput vs node count, with the algorithm schedule
    recorded per point: recursive-doubling/Bruck (log₂ n rounds) against
    the linear/pairwise baselines (n−1 rounds) — the message-count win
    PsPIN predicts dominates at scale."""
    rng = np.random.default_rng(2)
    for n in NODE_COUNTS:
        comm = mpi.Communicator(n, seed=3,
                                link_cfg=LinkConfig(loss=0.02, latency=2,
                                                    jitter=2))
        vals = [rng.normal(size=COLLECTIVE_BYTES // 8) for _ in range(n)]
        ref = np.sum(vals, axis=0)
        mats = [rng.integers(0, 256, (n, COLLECTIVE_BYTES // n))
                .astype(np.uint8) for _ in range(n)]

        runs = []
        for alg in ("rd", "linear"):
            t0 = comm.now
            h = mpi.iallreduce(comm, vals, op=np.add, algorithm=alg)
            comm.wait(h, max_ticks=400_000)
            assert all(np.allclose(o, ref) for o in h.result)
            runs.append(("allreduce", h, comm.now - t0))
        for alg in ("bruck", "pairwise"):
            t0 = comm.now
            h = mpi.ialltoall(comm, mats, algorithm=alg)
            comm.wait(h, max_ticks=400_000)
            assert all((h.result[r][i] == mats[i][r]).all()
                       for r in range(n) for i in range(n))
            runs.append(("alltoall", h, comm.now - t0))

        for kind, h, ticks in runs:
            total_bytes = n * COLLECTIVE_BYTES
            gbps = total_bytes * 8 / (ticks * TICK_NS)
            rec = dict(kind=f"mpi_{kind}", n_ranks=n,
                       bytes_per_rank=COLLECTIVE_BYTES, ticks=ticks,
                       algorithm=h.algorithm, rounds=h.rounds,
                       msgs_total=h.msgs_total,
                       goodput_gbps=round(float(gbps), 3))
            records.append(rec)
            row(f"mpi_{kind}_{h.algorithm}_n{n}", ticks * TICK_NS / 1e3,
                f"gbps={gbps:.2f};ticks={ticks};rounds={h.rounds};"
                f"msgs={h.msgs_total}")
        by_alg = {h.algorithm: h for _, h, _ in runs}
        assert by_alg["allreduce_rd"].rounds \
            <= by_alg["allreduce_linear"].rounds
        if n & (n - 1) == 0 and n > 2:
            # the headline criterion: log₂N vs N−1 rounds at 8 ranks
            assert by_alg["allreduce_rd"].rounds \
                < by_alg["allreduce_linear"].rounds


def _overlap_nonblocking(records: List[dict]) -> None:
    """Post ``iallreduce``, spin host compute while the plan progresses
    under the compute window, then poll: R = T_MM / (T_MM + T_Poll), the
    §V-C overlap methodology applied to a whole collective instead of a
    single typed receive.  Records the algorithm the size selector chose
    for every point."""
    n = 4
    comm = mpi.Communicator(n, seed=5,
                            link_cfg=LinkConfig(loss=0.0, latency=2,
                                                jitter=2))
    rng = np.random.default_rng(9)
    for nbytes, forced in ((4 << 10, None), (24 << 10, None),
                           (24 << 10, "tree")):
        vals = [rng.normal(size=nbytes // 8) for _ in range(n)]
        ref = np.sum(vals, axis=0)
        alg = forced or "auto"
        # calibrate: lossless completion time of this collective
        comm.rewire(link_cfg=LinkConfig(loss=0.0, latency=2, jitter=2),
                    seed=11)
        t0 = comm.now
        h = mpi.iallreduce(comm, vals, algorithm=alg)
        comm.wait(h, max_ticks=400_000)
        t_xfer0 = comm.now - t0
        t_mm = int(np.ceil(MM_FACTOR * t_xfer0))
        for loss in LOSSES:
            comm.rewire(link_cfg=LinkConfig(loss=loss, latency=2,
                                            jitter=2), seed=13)
            ratios = []
            for _ in range(ITERS):
                h = mpi.iallreduce(comm, vals, algorithm=alg)
                comm.progress(t_mm)           # the host compute window
                t0 = comm.now
                comm.wait(h, max_ticks=400_000)
                t_poll = comm.now - t0        # what compute failed to hide
                ratios.append(t_mm / (t_mm + t_poll))
                assert all(np.allclose(o, ref) for o in h.result)
            r_mean = float(np.mean(ratios))
            rec = dict(kind="mpi_overlap_nonblocking", n_ranks=n,
                       bytes_per_rank=nbytes, loss=loss,
                       algorithm=h.algorithm, rounds=h.rounds,
                       msgs_total=h.msgs_total, t_mm_ticks=t_mm,
                       overlap_ratio=round(r_mean, 4))
            records.append(rec)
            row(f"mpi_overlap_nb_{h.algorithm}_{nbytes >> 10}k"
                f"_loss{int(loss * 100)}",
                t_mm * TICK_NS / 1e3,
                f"R={r_mean:.4f};rounds={h.rounds}")


def _allreduce_large_sweep(records: List[dict]) -> None:
    """The large-message fast path head-to-head: Rabenseifner (the auto
    pick at these sizes) vs recursive doubling, 8 ranks, 256 KiB–4 MiB
    per rank, lossless and 2% loss.  Every point records the schedule
    metadata (rounds / msgs / bytes-on-wire) so the win is attributable:
    rd ships ⌈log₂ n⌉ full vectors per rank where Rabenseifner ships
    ~2·(n−1)/n of one — and at the largest size that bandwidth gap must
    show up in modeled ticks too (asserted)."""
    comm = mpi.Communicator(LARGE_RANKS, seed=0, cfg=large_cfg(),
                            link_cfg=LinkConfig(latency=1))
    rng = np.random.default_rng(21)
    for loss in LARGE_LOSSES:
        for nbytes in LARGE_SIZES:
            vals = [rng.integers(0, 1 << 20, nbytes // 8).astype(np.int64)
                    for _ in range(LARGE_RANKS)]
            ref = np.sum(np.stack(vals), axis=0)
            by_alg = {}
            for alg in ("rd", "auto"):
                comm.rewire(link_cfg=LinkConfig(loss=loss, latency=1),
                            seed=31)
                t0 = comm.now
                h = mpi.iallreduce(comm, vals, algorithm=alg)
                comm.wait(h, max_ticks=4_000_000)
                ticks = comm.now - t0
                assert all((o == ref).all() for o in h.result)
                stalls = sum(e["credit_stalls"] for e in comm.stats())
                gbps = nbytes * LARGE_RANKS * 8 / (ticks * TICK_NS)
                rec = dict(kind="allreduce_sweep", n_ranks=LARGE_RANKS,
                           bytes_per_rank=nbytes, loss=loss,
                           requested=alg, algorithm=h.algorithm,
                           rounds=h.rounds, msgs_total=h.msgs_total,
                           bytes_wire=h.bytes_wire, ticks=ticks,
                           credit_stalls=stalls,
                           goodput_gbps=round(float(gbps), 3))
                records.append(rec)
                by_alg[h.algorithm] = rec
                row(f"allreduce_{h.algorithm}_{nbytes >> 10}k"
                    f"_loss{int(loss * 100)}", ticks * TICK_NS / 1e3,
                    f"gbps={gbps:.2f};wireMB={h.bytes_wire / 2**20:.1f};"
                    f"rounds={h.rounds};msgs={h.msgs_total};"
                    f"stalls={stalls}")
            assert "allreduce_rab" in by_alg, \
                "auto must select Rabenseifner at large sizes"
            rab, rd = by_alg["allreduce_rab"], by_alg["allreduce_rd"]
            assert rab["bytes_wire"] < rd["bytes_wire"], (rab, rd)
            if nbytes == max(LARGE_SIZES):
                assert rab["ticks"] < rd["ticks"], (rab, rd)


def _grad_allreduce(records: List[dict]) -> None:
    """The trainer's gradient sync end-to-end: a ≥4 MiB gradient pytree
    per shard, reduced through :class:`repro.train.manual_dp.FabricGradSync`
    (nonblocking Rabenseifner over the fabric) with the progress hook
    driven from inside a modeled backprop window 1.25x the lossless
    transfer — §V-C overlap methodology applied to the data-parallel
    step.  At loss=0 the transfer must hide almost completely."""
    from repro.train.manual_dp import FabricGradSync
    n = 4
    comm = mpi.Communicator(n, seed=7, cfg=large_cfg(),
                            link_cfg=LinkConfig(latency=1))
    rng = np.random.default_rng(33)
    shapes = GRAD_SHAPES
    grads = [{k: rng.normal(size=s).astype(np.float32)
              for k, s in shapes.items()} for _ in range(n)]
    ref_mean = {k: np.mean(np.stack([g[k] for g in grads]), axis=0,
                           dtype=np.float64).astype(np.float32)
                for k in shapes}
    sync = FabricGradSync(comm)
    # calibrate: lossless completion with no compute overlap
    sync.post([{k: g[k].copy() for k in g} for g in grads])
    sync.wait()
    t_xfer0 = sync.last_stats["total_ticks"]
    t_mm = int(np.ceil(MM_FACTOR * t_xfer0))
    for loss in LARGE_LOSSES:
        comm.rewire(link_cfg=LinkConfig(loss=loss, latency=1), seed=13)
        sync.post([{k: g[k].copy() for k in g} for g in grads])
        left = t_mm
        while left > 0:                 # the backprop progress hook
            sync.progress(min(64, left))
            left -= 64
        means = sync.wait()
        st = sync.last_stats
        for m in means:
            for k in shapes:
                np.testing.assert_allclose(m[k], ref_mean[k], rtol=1e-5,
                                           atol=1e-6)
        gbps = st["grad_bytes"] * 8 / (st["total_ticks"] * TICK_NS)
        rec = dict(kind="grad_allreduce", n_ranks=n, loss=loss,
                   grad_bytes=st["grad_bytes"],
                   algorithm=st["algorithm"], rounds=st["rounds"],
                   msgs_total=st["msgs_total"],
                   bytes_wire=st["bytes_wire"], t_mm_ticks=t_mm,
                   poll_ticks=st["poll_ticks"],
                   overlap_ratio=round(st["overlap_ratio"], 4),
                   goodput_gbps=round(float(gbps), 3))
        records.append(rec)
        row(f"grad_allreduce_loss{int(loss * 100)}",
            st["total_ticks"] * TICK_NS / 1e3,
            f"R={st['overlap_ratio']:.4f};gbps={gbps:.2f};"
            f"alg={st['algorithm']}")
        if loss == 0.0:
            assert st["overlap_ratio"] >= 0.9, st


SCENARIOS = [
    ("overlap", _overlap_sweep),
    ("collective", _collective_sweep),
    ("overlap_nonblocking", _overlap_nonblocking),
    ("allreduce_large", _allreduce_large_sweep),
    ("grad_allreduce", _grad_allreduce),
]


def run(json_path: Optional[str] = JSON_PATH,
        scenario_filter: Optional[str] = None) -> List[dict]:
    records: List[dict] = []
    selected = [(n, fn) for n, fn in SCENARIOS
                if not scenario_filter or scenario_filter in n]
    if not selected:
        sys.exit(f"no bench_mpi scenario matches {scenario_filter!r}; "
                 f"available: " + ", ".join(n for n, _ in SCENARIOS))
    for name, fn in selected:
        timed_scenario(name, fn, records)
    if json_path:
        payload = dict(bench="mpi", tick_ns=TICK_NS, mm_factor=MM_FACTOR,
                       records=records)
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        row("mpi_json", 0.0, f"wrote={os.path.abspath(json_path)};"
            f"points={len(records)}")
    return records


if __name__ == "__main__":
    run(scenario_filter=sys.argv[1] if len(sys.argv) > 1 else None)
