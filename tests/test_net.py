"""Tests for repro.net: link model invariants, MAC routing, two-node
SLMP reliability under loss, ping-pong, fabric checkpointing, and the
two-phase fabric tick: every busy node's NIC step launched, then one
device read for all of them; and the host's writes into NIC state, one
program each."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import mpi, obs
from repro.core import apps, handlers as H, packet as pkt, slmp, spin_nic
from repro.mpi import wire
from repro.net import (Fabric, Link, LinkConfig, Node, PingPongClient,
                       SlmpSenderEngine)
from repro.net import fabric as fabric_mod
from repro.net.node import HostEngine


def _frames(n, nbytes=32):
    return [pkt.make_udp(np.arange(nbytes, dtype=np.uint8))
            for _ in range(n)]


# ----------------------------------------------------------------- link
def test_link_lossless_delivers_everything():
    lk = Link(LinkConfig(loss=0.0, latency=2, capacity=64))
    st = lk.push(lk.init_state(), jax.random.PRNGKey(0),
                 pkt.stack_frames(_frames(16)), now=0)
    st, out = lk.pop(st, now=1, n=16)
    assert int(np.asarray(out.valid).sum()) == 0      # latency not elapsed
    st, out = lk.pop(st, now=2, n=16)
    assert int(np.asarray(out.valid).sum()) == 16
    assert lk.stats(st)["lost"] == 0
    # delivered frames carry their original bytes
    i = int(np.argmax(np.asarray(out.valid)))
    ln = int(np.asarray(out.length)[i])
    np.testing.assert_array_equal(np.asarray(out.data)[i, :ln],
                                  _frames(1)[0])


def test_link_total_loss_delivers_nothing():
    lk = Link(LinkConfig(loss=1.0, latency=1, capacity=64))
    st = lk.push(lk.init_state(), jax.random.PRNGKey(0),
                 pkt.stack_frames(_frames(8)), now=0)
    assert lk.stats(st)["lost"] == 8
    st, out = lk.pop(st, now=10, n=8)
    assert int(np.asarray(out.valid).sum()) == 0


def test_link_loss_is_deterministic_in_key():
    lk = Link(LinkConfig(loss=0.5, latency=1, capacity=64))
    batch = pkt.stack_frames(_frames(32))
    s1 = lk.push(lk.init_state(), jax.random.PRNGKey(7), batch, 0)
    s2 = lk.push(lk.init_state(), jax.random.PRNGKey(7), batch, 0)
    s3 = lk.push(lk.init_state(), jax.random.PRNGKey(8), batch, 0)
    assert lk.stats(s1) == lk.stats(s2)
    np.testing.assert_array_equal(np.asarray(s1.occupied),
                                  np.asarray(s2.occupied))
    assert 0 < lk.stats(s1)["lost"] < 32               # p=.5, n=32
    assert lk.stats(s3) != lk.stats(s1) or not np.array_equal(
        np.asarray(s3.deliver_at), np.asarray(s1.deliver_at))


def test_link_duplication_and_capacity_overflow():
    lk = Link(LinkConfig(loss=0.0, duplicate=1.0, latency=1, capacity=12))
    st = lk.push(lk.init_state(), jax.random.PRNGKey(0),
                 pkt.stack_frames(_frames(8)), now=0)
    s = lk.stats(st)
    assert s["duplicated"] == 8
    assert s["overflowed"] == 4                        # 16 candidates, 12 slots
    st, out = lk.pop(st, now=5, n=16)
    assert int(np.asarray(out.valid).sum()) == 12


def test_link_jitter_reorders():
    lk = Link(LinkConfig(loss=0.0, latency=1, jitter=6, capacity=128))
    st = lk.init_state()
    key = jax.random.PRNGKey(1)
    # stamp each frame's payload with its send order
    frames = []
    for i in range(32):
        f = pkt.make_udp(np.full(16, i, np.uint8))
        frames.append(f)
    st = lk.push(st, key, pkt.stack_frames(frames), now=0)
    seen = []
    for t in range(1, 12):
        st, out = lk.pop(st, now=t, n=32)
        v = np.asarray(out.valid)
        for i in np.flatnonzero(v):
            seen.append(int(np.asarray(out.data)[i, pkt.SLMP_BASE]))
    assert sorted(seen) == list(range(32))             # all arrive
    assert seen != list(range(32))                     # ...but not in order


# --------------------------------------------------------------- fabric
def _slmp_pair(nbytes, loss, seed=7, window=8, timeout=10, jitter=2,
               duplicate=0.0):
    rng = np.random.default_rng(0)
    msg = rng.integers(0, 256, nbytes).astype(np.uint8)
    cfg = slmp.SlmpSenderConfig(
        window=window, mtu_payload=1024, timeout=timeout,
        src_mac=pkt.node_mac(0), dst_mac=pkt.node_mac(1))
    sender = SlmpSenderEngine(msg, msg_id=42, cfg=cfg)
    a = Node("sender", pkt.node_mac(0), [apps.make_null_context()],
             engines=[sender], batch=16)
    b = Node("recv", pkt.node_mac(1), [slmp.make_slmp_context()],
             batch=16, host_bytes=1 << 17)
    fab = Fabric([a, b],
                 link_cfg=LinkConfig(loss=loss, latency=2, jitter=jitter,
                                     duplicate=duplicate),
                 seed=seed)
    return fab, sender, b, msg


def test_fabric_slmp_lossless():
    fab, sender, b, msg = _slmp_pair(20_000, loss=0.0)
    fab.run(max_ticks=500)
    assert sender.done and not sender.failed
    assert sender.sender.retransmits == 0
    np.testing.assert_array_equal(b.read_host(0, len(msg)), msg)
    assert b.completions == [42]


def test_fabric_slmp_survives_heavy_loss():
    """Acceptance criterion: a multi-segment message completes at >=10%
    simulated loss, and the retransmission path actually fires."""
    fab, sender, b, msg = _slmp_pair(40_000, loss=0.15)
    fab.run(max_ticks=5000)
    assert sender.done and not sender.failed
    assert sender.sender.nseg > 10                      # multi-segment
    assert sender.sender.retransmits > 0                # retransmit fired
    assert fab.link_stats()[1]["lost"] > 0              # loss really applied
    np.testing.assert_array_equal(b.read_host(0, len(msg)), msg)
    assert 42 in b.completions


def test_fabric_slmp_survives_duplication_and_reordering():
    fab, sender, b, msg = _slmp_pair(20_000, loss=0.1, jitter=5,
                                     duplicate=0.2)
    fab.run(max_ticks=5000)
    assert sender.done
    np.testing.assert_array_equal(b.read_host(0, len(msg)), msg)


def test_fabric_unroutable_frames_counted():
    cfg = slmp.SlmpSenderConfig(window=2, mtu_payload=512,
                                src_mac=pkt.node_mac(0),
                                dst_mac=b"\xff\xff\xff\xff\xff\xff")
    sender = SlmpSenderEngine(np.zeros(1024, np.uint8), 1, cfg)
    a = Node("a", pkt.node_mac(0), [apps.make_null_context()],
             engines=[sender], batch=8)
    fab = Fabric([a], seed=0)
    for _ in range(3):
        fab.tick()
    assert fab.unroutable > 0


def test_fabric_rejects_nodes_of_unequal_batch():
    a = Node("a", pkt.node_mac(0), [apps.make_null_context()], batch=8)
    b = Node("b", pkt.node_mac(1), [apps.make_null_context()], batch=16)
    with pytest.raises(ValueError, match=r"\[8, 16\]"):
        Fabric([a, b])


def test_fabric_pingpong_rtt():
    client = PingPongClient(count=3, proto="udp", src_mac=pkt.node_mac(0),
                            dst_mac=pkt.node_mac(1))
    a = Node("client", pkt.node_mac(0), [apps.make_null_context()],
             engines=[client], batch=8)
    b = Node("server", pkt.node_mac(1),
             [apps.make_udp_pingpong_context()], batch=8)
    fab = Fabric([a, b], link_cfg=LinkConfig(loss=0.0, latency=1), seed=0)
    fab.run(max_ticks=100)
    assert client.done
    assert client.rtts == [2, 2, 2]        # 1 tick out + 1 tick back


def test_fabric_checkpoint_restore_is_deterministic():
    fab, sender, b, msg = _slmp_pair(20_000, loss=0.15, seed=5)
    for _ in range(10):
        fab.tick()
    snap = fab.checkpoint()
    fab.run(max_ticks=2000)
    end1 = (fab.now, sender.sender.retransmits,
            b.read_host(0, len(msg)).copy())
    fab.restore(snap)
    fab.run(max_ticks=2000)
    end2 = (fab.now, sender.sender.retransmits,
            b.read_host(0, len(msg)).copy())
    assert end1[0] == end2[0] and end1[1] == end2[1]
    np.testing.assert_array_equal(end1[2], end2[2])
    np.testing.assert_array_equal(end1[2], msg)


def test_node_drains_counters_from_packet_mode_contexts():
    """Contexts without message_mode can still push_counter (icmp-host
    mode): the node must drain their notifications too."""
    client = PingPongClient(count=2, proto="icmp", src_mac=pkt.node_mac(0),
                            dst_mac=pkt.node_mac(1), timeout=8)
    a = Node("client", pkt.node_mac(0), [apps.make_null_context()],
             engines=[client], batch=8)
    b = Node("hostmode", pkt.node_mac(1), [apps.make_icmp_host_context()],
             batch=8)
    fab = Fabric([a, b], link_cfg=LinkConfig(loss=0.0, latency=1), seed=0)
    for _ in range(6):
        fab.tick()
    # icmp-host handler pushes pkt_len per matched frame; no replies come
    # back, so the client refires after its timeout — at least one push
    assert len(b.completions) >= 1


def test_slmp_sender_gives_up_after_max_retries():
    cfg = slmp.SlmpSenderConfig(window=2, mtu_payload=512, timeout=2,
                                max_retries=3)
    sender = slmp.SlmpSender(np.zeros(2048, np.uint8), 9, cfg)
    now = 0
    while not (sender.done or sender.failed):
        sender.poll(now)                   # frames vanish: 100% loss
        now += 1
        assert now < 1000
    assert sender.failed and not sender.done


# ------------------------------------------------ the two-phase fabric tick
def _reference_tick(node, ingress, now):
    """``Node.tick`` stepping by itself, with each of the step's outputs
    read on its own: valid, then data and length of each batch, and the
    completion FIFO drained through ``SpinNIC.pop_counters``."""
    def frames(batch):
        valid = np.asarray(batch.valid)
        if not valid.any():
            return []
        data, lens = np.asarray(batch.data), np.asarray(batch.length)
        return [data[i, :lens[i]].copy() for i in np.flatnonzero(valid)]

    node.state, egress, to_host = node.nic.step(node.state, ingress)
    host_frames = frames(to_host)
    if host_frames:
        for e in node.engines:
            e.on_host_frames(host_frames, now)
    if node._completes:
        comp, node.state = node.nic.pop_counters(node.state,
                                                 slmp.COMPLETION_QUEUE)
        if len(comp):
            node.completions.extend(int(c) for c in comp)
            for e in node.engines:
                e.on_completions(comp, now)
    out = frames(egress)
    for e in node.engines:
        out.extend(e.poll(now))
    return out


def _one_node_at_a_time(fab):
    """``Fabric.tick`` with each busy node stepping, reading and running
    its engines before the next node steps."""
    now = fab.now
    fab._stack, ing = fabric_mod._pop_all(fab.batch, fab._stack, now)
    valid = np.asarray(ing.valid)
    data, length = np.asarray(ing.data), np.asarray(ing.length)
    outbound = [[] for _ in fab.nodes]
    for i, node in enumerate(fab.nodes):
        if valid[i].any():
            frames = node.tick(pkt.PacketBatch(data[i], length[i], valid[i]),
                               now)
        else:
            frames = node.tick_idle(now)
        fab._route(frames, outbound)
    fab._flush_outbound(outbound)
    fab.now += 1


def _lossy_transfers():
    """Three SLMP messages over a lossy, duplicating, reordering wire: the
    receiver's tail handler pushes a completion on every EOM arrival and
    its ACKs reach the sender's host path."""
    rng = np.random.default_rng(0)
    cfg = slmp.SlmpSenderConfig(window=4, mtu_payload=512, timeout=6,
                                src_mac=pkt.node_mac(0),
                                dst_mac=pkt.node_mac(1))
    senders = [SlmpSenderEngine(rng.integers(0, 256, 3000).astype(np.uint8),
                                msg_id=40 + i, cfg=cfg) for i in range(3)]
    a = Node("sender", pkt.node_mac(0), [apps.make_null_context()],
             engines=senders, batch=8)
    b = Node("recv", pkt.node_mac(1), [slmp.make_slmp_context()], batch=8,
             host_bytes=1 << 14)
    fab = Fabric([a, b], link_cfg=LinkConfig(loss=0.15, latency=2, jitter=2,
                                             duplicate=0.2), seed=3)

    def run():
        fab.run(max_ticks=3000)
        assert all(s.done and not s.failed for s in senders)
        assert len(b.completions) > 3                 # duplicates too
        return b.completions
    return fab, run


def _allreduce_8r():
    """A segmented Rabenseifner allreduce of 32 KiB a rank on 8 ranks at
    2 % loss: 2 KiB segments over the credit-managed rendezvous.  Beside
    it, rank 1 sends rank 2 a rendezvous chunk whose receive is posted
    only when rank 0 completes a receive of its own: a completion on rank
    0 grants the waiting RTS on rank 2 and arms rank 2's expect table."""
    cfg = mpi.MpiConfig(eager_threshold=1024, eager_slot_bytes=4096,
                        coll_seg_bytes=2048, n_rdv_slots=4)
    comm = mpi.Communicator(8, cfg=cfg, seed=5,
                            link_cfg=LinkConfig(loss=0.02, latency=1))
    rng = np.random.default_rng(1)
    vals = [rng.standard_normal(8192).astype(np.float32) for _ in range(8)]
    chunk = rng.integers(0, 256, cfg.coll_seg_bytes).astype(np.uint8)

    def run():
        got, late_recv = np.zeros_like(chunk), []
        comm.isend(1, 2, chunk, tag=7, datatype=comm.seg_dtype)
        cue = comm.irecv(0, np.zeros(64, np.uint8), source=3, tag=8)
        cue.add_done_callback(lambda _: late_recv.append(
            comm.irecv(2, got, source=1, tag=7)))
        comm.isend(3, 0, np.ones(64, np.uint8), tag=8)
        h = mpi.iallreduce(comm, vals, algorithm="rab")
        comm.wait(h, max_ticks=20_000)
        comm.wait(*late_recv, max_ticks=20_000)
        np.testing.assert_array_equal(got, chunk)
        want = np.sum(np.stack(vals), axis=0, dtype=np.float64)
        for r in h.result:
            np.testing.assert_allclose(r, want, rtol=1e-5, atol=1e-5)
        stats = comm.stats()
        assert sum(s["rdv_sent"] for s in stats) > 0
        assert sum(s["retransmits"] for s in stats) > 0
        return dict(ticks=comm.now, stats=stats, links=comm.link_stats(),
                    result=[r.tobytes() for r in h.result])
    return comm.fabric, run


def _host_tree(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _recorded(monkeypatch, scenario, reference):
    """Runs ``scenario`` with the two-phase tick, or with the reference
    (one node at a time, separate reads), and logs tick by tick on every
    node: the frames it returns, the host-path frames and completions its
    engines are handed, and after the tick its completions drained so far
    and every leaf of its NIC state.  Also counts the ``write_expect``
    calls that land on a node between its step's launch and its finish."""
    if reference:
        monkeypatch.setattr(Fabric, "tick", _one_node_at_a_time)
        monkeypatch.setattr(Node, "tick", _reference_tick)
    fab, run = scenario()
    log, late = [], []
    tick, tick_idle = Node.tick, Node.tick_idle
    fabric_tick, write_expect = Fabric.tick, Node.write_expect

    def frames(fs):
        return [f.tobytes() for f in fs]

    def ticking(node, ingress, now):
        out = tick(node, ingress, now)
        log.append(("out", node.name, now, frames(out)))
        return out

    def idling(node, now):
        out = tick_idle(node, now)
        log.append(("out", node.name, now, frames(out)))
        return out

    def ticked(f):
        fabric_tick(f)
        for n in f.nodes:
            log.append(("state", n.name, f.now, len(n.completions),
                        [x.tobytes() for x in _host_tree(n.state)]))

    def writing(node, idx, msg_id):
        late.append(node._fetched is not None)
        write_expect(node, idx, msg_id)

    def hooked(node, engine, name):
        call = getattr(engine, name)

        def handed(values, now):
            got = frames(values) if name == "on_host_frames" \
                else values.tolist()
            log.append((name, node.name, now, got))
            call(values, now)
        return handed

    monkeypatch.setattr(Node, "tick", ticking)
    monkeypatch.setattr(Node, "tick_idle", idling)
    monkeypatch.setattr(Fabric, "tick", ticked)
    monkeypatch.setattr(Node, "write_expect", writing)
    for n in fab.nodes:
        for e in n.engines:
            for name in ("on_host_frames", "on_completions"):
                monkeypatch.setattr(e, name, hooked(n, e, name))
    return fab, run(), log, sum(late)


@pytest.mark.parametrize("scenario", (_lossy_transfers, _allreduce_8r),
                         ids=("slmp_lossy", "allreduce_8r_rdv"))
def test_two_phase_tick_matches_one_node_at_a_time(monkeypatch, scenario):
    """Every busy node's step launched, then one read for all of them,
    then each node finished in order: tick by tick on every node the same
    frames, host-path deliveries, completions and NIC state as stepping
    and reading one node at a time with a read for each output."""
    with monkeypatch.context() as m:
        fab, result, log, late = _recorded(m, scenario, reference=False)
    with monkeypatch.context() as m:
        ref, ref_result, ref_log, _ = _recorded(m, scenario, reference=True)
    assert result == ref_result
    assert len(log) == len(ref_log)
    for got, want in zip(log, ref_log):
        assert got == want, got[:3]
    assert {entry[0] for entry in log} >= {"out", "state", "on_host_frames"}
    assert any(e[0] == "out" and e[3] for e in log)       # egress left
    assert any(n.completions for n in fab.nodes)
    for n, r in zip(fab.nodes, ref.nodes):
        assert n.completions == r.completions
    if scenario is _allreduce_8r:
        # a completion on rank 0 armed rank 2's expect table after rank
        # 2's step was launched, where the reference wrote it before
        # that step
        assert late > 0


def _spans_by_tick(monkeypatch):
    """The program spans each ``Fabric.tick`` opens, one list a tick."""
    ticks, opened = [], []
    span, tick = obs.span, Fabric.tick

    def counting(name):
        opened.append(name)
        return span(name)

    def ticked(fab):
        first = len(opened)
        tick(fab)
        ticks.append(opened[first:])
    monkeypatch.setattr(obs, "span", counting)
    monkeypatch.setattr(Fabric, "tick", ticked)
    return ticks


def test_busy_node_tick_opens_one_read_span(monkeypatch):
    """On the lossy transfers, a tick with any busy node reads the
    ingress pair and then the steps' outputs under one ``d2h.to_host``,
    with one or both nodes busy; an idle tick reads only ``valid``."""
    ticks = _spans_by_tick(monkeypatch)
    _, run = _lossy_transfers()
    run()
    assert ticks
    busy_counts = set()
    for spans in ticks:
        busy = spans.count("nic.step")
        busy_counts.add(busy)
        reads = [n for n in spans if n.startswith("d2h.")]
        assert reads == (["d2h.ingress", "d2h.ingress", "d2h.to_host"]
                         if busy else ["d2h.ingress"])
    assert busy_counts == {0, 1, 2}
    # some of these ticks drained completions
    assert any("engine.completions" in spans for spans in ticks)


class _Once(HostEngine):
    """Puts one frame on the wire at its first poll."""

    def __init__(self, frame):
        self.frame = frame

    def poll(self, now):
        out, self.frame = [] if self.frame is None else [self.frame], None
        return out


@pytest.mark.parametrize("n_busy", (1, 2, 8))
def test_fabric_tick_reads_every_busy_step_at_once(monkeypatch, n_busy):
    """Of 8 nodes, ``n_busy`` receive a frame on the same tick: that tick
    launches ``n_busy`` NIC steps and opens one ``d2h.to_host``; a tick
    with no busy node opens none."""
    ticks = _spans_by_tick(monkeypatch)
    macs = [pkt.node_mac(i) for i in range(8)]
    nodes = [Node(f"n{i}", macs[i], [apps.make_null_context()], batch=8,
                  engines=[_Once(pkt.make_udp(
                      np.arange(32, dtype=np.uint8), src_mac=macs[i],
                      dst_mac=macs[(i + 1) % 8]))] if i < n_busy else [])
             for i in range(8)]
    fab = Fabric(nodes, link_cfg=LinkConfig(loss=0.0, latency=1), seed=0)
    for _ in range(3):
        fab.tick()
    steps = [spans.count("nic.step") for spans in ticks]
    reads = [spans.count("d2h.to_host") for spans in ticks]
    assert steps == [0, n_busy, 0]
    assert reads == [0, 1, 0]


def test_node_tick_drains_an_overrun_fifo_as_pop_counters():
    """More than a FIFO's length of completions between two drains: only
    the newest ``COUNTER_QUEUE_LEN`` survive, oldest first."""
    node = Node("hostmode", pkt.node_mac(1), [apps.make_icmp_host_context()],
                batch=32)
    pushed = 0
    for step in range(3):
        # ICMP echoes of distinct lengths: each push carries its frame's
        # length, so the drained order is visible
        frames = [pkt.make_icmp_echo(np.zeros(8 + 32 * step + i, np.uint8),
                                     seq=i) for i in range(32)]
        node.state, _, _ = node.nic.step(node.state,
                                         pkt.stack_frames(frames, n=32))
        pushed += 32
    assert pushed > H.COUNTER_QUEUE_LEN
    before = jax.tree.map(jnp.copy, node.state)
    want, _ = node.nic.pop_counters(before, slmp.COMPLETION_QUEUE)
    assert len(want) == H.COUNTER_QUEUE_LEN
    assert len(set(want.tolist())) == H.COUNTER_QUEUE_LEN

    empty = pkt.stack_frames([], n=32)
    node.tick(empty, now=0)
    assert node.completions == want.tolist()
    assert int(node.state.counter_count[slmp.COMPLETION_QUEUE]) == 0
    node.tick(empty, now=1)                    # a drain is not a peek
    assert node.completions == want.tolist()


# ------------------------------------------------- host writes into NIC state
def _written_nic():
    """A NIC whose expect table and counter counts hold distinct non-zero
    words, so a write that touches the wrong slot shows."""
    nic = spin_nic.SpinNIC([apps.make_mpi_ddt_context(
        np.zeros((1, 8), np.int32), np.ones(1, np.int32), region_bytes=64,
        n_slots=8, port=9331)], host_bytes=1 << 10)
    st = nic.init_state()
    n = st.expect.shape[0]
    return nic, dataclasses.replace(
        st, expect=jnp.asarray(np.arange(1, n + 1) * 0x01010101, jnp.uint32),
        counter_count=jnp.arange(5, 5 + H.N_COUNTER_QUEUES,
                                 dtype=jnp.int32))


@pytest.mark.parametrize("msg_id", (
    0, 1, wire.pack_msg_id(wire.MPI_KIND_RDV, 3, 0xABC), 0xFFFFFFFF),
    ids=("disarm", "one", "packed", "uint32_max"))
def test_write_expect_matches_the_eager_write(msg_id):
    """Every slot, armed with every kind of uint32 msg_id, as the eager
    ``.at[].set`` sets it; the state written into stays readable."""
    nic, st = _written_nic()
    before = np.asarray(st.expect)
    assert before.size == 8
    for idx in range(before.size):
        got = nic.write_expect(st, idx, msg_id)
        want = st.expect.at[idx].set(jnp.uint32(msg_id))
        assert got.expect.dtype == jnp.uint32
        np.testing.assert_array_equal(np.asarray(got.expect),
                                      np.asarray(want))
        np.testing.assert_array_equal(np.asarray(st.expect), before)
        assert got.counter_count is st.counter_count


@pytest.mark.parametrize("queue", range(H.N_COUNTER_QUEUES))
def test_clear_counters_matches_the_eager_write(queue):
    nic, st = _written_nic()
    before = np.asarray(st.counter_count)
    got = nic.clear_counters(st, queue)
    np.testing.assert_array_equal(np.asarray(got.counter_count),
                                  np.asarray(st.counter_count.at[queue].set(0)))
    np.testing.assert_array_equal(np.asarray(st.counter_count), before)
    assert got.expect is st.expect


@pytest.mark.parametrize("write,uploads", (
    (lambda nic, st: nic.write_expect(st, 5, 0xFFFFFFFF), 1),
    (lambda nic, st: nic.clear_counters(st, slmp.COMPLETION_QUEUE), 0)),
    ids=("write_expect", "clear_counters"))
def test_host_write_executes_one_program(tmp_path, write, uploads):
    """A warm host write into NIC state, under the profiler: the events
    inside its ``nic.write`` span hold one program execution and at most
    the one upload of its operands, not a chain of eager primitives."""
    nic, st = _written_nic()
    jax.block_until_ready(write(nic, st))             # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(write(nic, st))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = [ev for plane in jax.profiler.ProfileData.from_file(
        str(path)).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events]
    (span,) = [ev for ev in events if ev.name == "nic.write"]
    inside = [ev.name for ev in events
              if span.start_ns <= ev.start_ns < span.start_ns
              + span.duration_ns]
    assert sum(n.endswith("Executable::Execute") for n in inside) == 1, \
        inside
    assert inside.count("DevicePut") == uploads, inside
