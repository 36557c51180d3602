"""A fabric node: one SpinNIC plus the host software beside it.

The paper's end-to-end experiments always pair the sNIC with host-side
protocol code — the SLMP sender that segments, windows and retransmits,
and the ping-pong client that stamps RTTs.  A :class:`Node` bundles a
:class:`~repro.core.spin_nic.SpinNIC` (+ its ``NICState``) with a list of
*host engines* that generate and consume traffic from inside the
simulation:

  * handler egress (ACKs, echo replies) leaves through the node's wire;
  * frames the matcher does not claim are forwarded ``to_host`` — exactly
    the Corundum/host datapath — and the engines consume them there
    (ACKs land at the SLMP sender, pongs at the ping-pong client);
  * completion notifications (counter queue 0) are drained every tick.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import handlers as H
from repro.core import packet as pkt
from repro.core import slmp
from repro.core import spin_nic


class HostEngine:
    """Host-side traffic generator/consumer stepped by the fabric tick."""

    def poll(self, now: int) -> List[np.ndarray]:
        """Frames this engine puts on the wire at tick ``now``."""
        return []

    def on_host_frames(self, frames: List[np.ndarray], now: int) -> None:
        """Frames forwarded to the host datapath (non-matching ingress)."""

    def on_completions(self, values: np.ndarray, now: int) -> None:
        """Values drained from the completion counter FIFO."""

    @property
    def done(self) -> bool:
        return True

    def snapshot(self) -> dict:
        return {}

    def restore(self, snap: dict) -> None:
        pass


class SlmpSenderEngine(HostEngine):
    """Host half of a reliable SLMP transfer (wraps core.slmp.SlmpSender)."""

    def __init__(self, msg: np.ndarray, msg_id: int,
                 cfg: Optional[slmp.SlmpSenderConfig] = None):
        self.sender = slmp.SlmpSender(msg, msg_id, cfg)

    def poll(self, now: int) -> List[np.ndarray]:
        return self.sender.poll(now)

    def on_host_frames(self, frames: List[np.ndarray], now: int) -> None:
        for msg_id, off in slmp.parse_acks(pkt.stack_frames(frames)) \
                if frames else []:
            self.sender.on_ack(msg_id, off)

    @property
    def done(self) -> bool:
        # "done" = generates no more traffic: delivered OR gave up
        return self.sender.done or self.sender.failed

    @property
    def failed(self) -> bool:
        return self.sender.failed

    def snapshot(self) -> dict:
        return self.sender.snapshot()

    def restore(self, snap: dict) -> None:
        self.sender.restore(snap)


class PingPongClient(HostEngine):
    """Fires ``count`` pings at a peer, one outstanding, recording the RTT
    of each pong in fabric ticks (the Fig-7 client, ICMP or UDP)."""

    def __init__(self, count: int, payload: int = 56, proto: str = "udp",
                 dport: int = 9999, src_mac: Optional[bytes] = None,
                 dst_mac: Optional[bytes] = None, timeout: int = 64):
        assert proto in ("icmp", "udp")
        assert payload >= 2, "seq stamp needs two payload bytes"
        self.count = count
        self.proto = proto
        self.dport = dport
        self.src_mac = src_mac
        self.dst_mac = dst_mac
        self.timeout = timeout
        self.payload = np.arange(payload, dtype=np.uint8)
        self.seq = 0
        self.sent_at = -1          # -1: nothing outstanding
        self.first_sent = -1       # first transmission of the current seq
        self.rtts: List[int] = []
        self.timeouts = 0

    def _frame(self, seq: int) -> np.ndarray:
        # the responder echoes the payload verbatim, so a seq stamped into
        # the first two payload bytes identifies which ping a pong answers
        payload = self.payload.copy()
        payload[0], payload[1] = (seq >> 8) & 0xFF, seq & 0xFF
        if self.proto == "icmp":
            return pkt.make_icmp_echo(payload, seq=seq,
                                      src_mac=self.src_mac,
                                      dst_mac=self.dst_mac)
        return pkt.make_udp(payload, dport=self.dport,
                            src_mac=self.src_mac, dst_mac=self.dst_mac)

    def poll(self, now: int) -> List[np.ndarray]:
        if self.seq >= self.count and self.sent_at < 0:
            return []
        if self.sent_at >= 0:
            if now - self.sent_at < self.timeout:
                return []
            self.timeouts += 1                 # lost ping or pong: refire
        else:
            self.first_sent = now
        self.sent_at = now
        return [self._frame(self.seq)]

    def on_host_frames(self, frames: List[np.ndarray], now: int) -> None:
        if self.sent_at < 0:
            return
        for f in frames:
            is_pong = (f[pkt.IP_PROTO] == pkt.IPPROTO_ICMP
                       and f[pkt.ICMP_TYPE] == pkt.ICMP_ECHO_REPLY) \
                if self.proto == "icmp" else \
                (f[pkt.IP_PROTO] == pkt.IPPROTO_UDP)
            # both echo payloads start at byte 42: the stamped seq ties the
            # pong to the outstanding ping (duplicates/late pongs ignored)
            echoed = (int(f[42]) << 8) | int(f[43]) if len(f) >= 44 else -1
            if is_pong and echoed == self.seq:
                # completion latency: measured from the FIRST transmission,
                # so retry delay after loss shows up in the number
                self.rtts.append(now - self.first_sent)
                self.seq += 1
                self.sent_at = -1
                break

    @property
    def done(self) -> bool:
        return self.seq >= self.count

    def snapshot(self) -> dict:
        return dict(seq=self.seq, sent_at=self.sent_at,
                    first_sent=self.first_sent,
                    rtts=list(self.rtts), timeouts=self.timeouts)

    def restore(self, snap: dict) -> None:
        self.seq = snap["seq"]
        self.sent_at = snap["sent_at"]
        self.first_sent = snap["first_sent"]
        self.rtts = list(snap["rtts"])
        self.timeouts = snap["timeouts"]


def _live_frames(batch: pkt.PacketBatch) -> List[np.ndarray]:
    """The valid frames of a host copy of a batch, each trimmed to its
    length, in slot order."""
    return [batch.data[i, :batch.length[i]].copy()
            for i in np.flatnonzero(batch.valid)]


def fetch(nodes: Sequence["Node"]) -> None:
    """One blocking read of the outputs of every launched step of
    ``nodes``: ``device_get`` starts every copy before it waits on any, so
    the wait covers only the device work still queued."""
    with obs.span("d2h.to_host"):
        host = jax.device_get([n._launched for n in nodes])
    for n, out in zip(nodes, host):
        n._launched, n._fetched = None, out


class Node:
    """One endpoint of the fabric: NIC + host engines + a MAC address.

    Pass ``nic`` to share one :class:`SpinNIC` (and its jitted datapath)
    between several nodes with identical contexts — a ``SpinNIC`` holds no
    per-node mutable state, so an N-rank fabric compiles the step function
    once instead of N times.  ``contexts``/``host_bytes``/``batch`` are
    ignored when ``nic`` is given.
    """

    def __init__(self, name: str, mac: bytes,
                 contexts: Optional[Sequence] = None,
                 host_bytes: int = 1 << 20,
                 batch: int = 32,
                 engines: Sequence[HostEngine] = (),
                 nic: Optional[spin_nic.SpinNIC] = None):
        self.name = name
        self.mac = bytes(mac)
        if nic is None:
            assert contexts is not None, "need contexts or a prebuilt nic"
            nic = spin_nic.SpinNIC(list(contexts), host_bytes=host_bytes,
                                   batch=batch)
        self.nic = nic
        contexts = nic.contexts
        self.batch = nic.batch
        # any installed handler may push_counter; leave the completion FIFO
        # out of each step's read and drain only when no context runs
        # handlers at all (null-context sender/client nodes)
        self._completes = any(
            c.message_mode or c.header is not H.default_handler
            or c.packet is not H.default_handler
            or c.tail is not H.default_handler
            for c in contexts)
        self.state = self.nic.init_state()
        self.engines: List[HostEngine] = list(engines)
        # drained completion FIFO values, in arrival order.  SLMP pushes
        # are at-least-once (one per EOM *arrival* — see slmp_tail_handler)
        # so duplicates appear under loss; membership, not equality, is the
        # meaningful check.
        self.completions: List[int] = []
        # a launched step's outputs: on the device until fetched, then
        # their host copy until the tick that finishes the step
        self._launched: Optional[tuple] = None
        self._fetched: Optional[tuple] = None

    def tick_idle(self, now: int) -> List[np.ndarray]:
        """Advance one tick with an empty ingress batch.  The NIC step is
        skipped entirely: with no valid frames the datapath is a no-op on
        every piece of state except the cycle counter (which nothing
        reads), and the jitted step costs the same whether the batch is
        empty or full — skipping it is what makes a mostly-idle fabric
        tick cheap.  Host engines still poll (timers, retransmits)."""
        return self._poll(now)

    def _poll(self, now: int) -> List[np.ndarray]:
        out: List[np.ndarray] = []
        with obs.span("engine.poll"):
            for e in self.engines:
                out.extend(e.poll(now))
        return out

    def launch(self, ingress: pkt.PacketBatch) -> None:
        """Dispatch the NIC step on ``ingress`` and hold what it gives the
        host — host-path frames, handler egress and, where handlers may
        push, the completion FIFO — on the device, unread.  The next
        :meth:`tick` finishes the step; :func:`fetch` reads the held
        outputs of many nodes at once before that."""
        with obs.span("nic.step"):
            self.state, egress, to_host = self.nic.step(self.state, ingress)
        fifo = ((self.state.counter_count, self.state.counters)
                if self._completes else None)
        self._launched = (to_host, egress, fifo)

    def tick(self, ingress: pkt.PacketBatch, now: int) -> List[np.ndarray]:
        """Advance one tick: run the NIC on the delivered ingress batch,
        hand host-path frames and completions to the engines, and return
        every frame this node puts on the wire.  A step already
        :meth:`launch`-ed on ``ingress`` is finished, not run again."""
        if self._fetched is None:
            if self._launched is None:
                self.launch(ingress)
            fetch([self])
        (to_host, egress, fifo), self._fetched = self._fetched, None

        # host datapath: deliver non-matching frames to the engines
        host_frames = _live_frames(to_host)
        if host_frames:
            with obs.span("engine.frames"):
                for e in self.engines:
                    e.on_host_frames(host_frames, now)

        # completion notifications
        if fifo is not None:
            q = slmp.COMPLETION_QUEUE
            count, rings = fifo
            if count[q]:
                comp = spin_nic.drained(rings[q], int(count[q]))
                self.state = self.nic.clear_counters(self.state, q)
                self.completions.extend(int(c) for c in comp)
                with obs.span("engine.completions"):
                    for e in self.engines:
                        e.on_completions(comp, now)

        # outbound = handler egress + engine-generated frames
        out = _live_frames(egress)
        out.extend(self._poll(now))
        return out

    @property
    def done(self) -> bool:
        return all(e.done for e in self.engines)

    def reset(self, engines: Optional[Sequence[HostEngine]] = None) -> None:
        """Fresh NIC state (and optionally new engines) without recompiling
        the jitted datapath — sweep benchmarks reuse one Node per config."""
        self.state = self.nic.init_state()
        self.completions = []
        self._launched = self._fetched = None
        if engines is not None:
            self.engines = list(engines)

    def read_host(self, base: int, nbytes: int) -> np.ndarray:
        return self.nic.read_host(self.state, base, nbytes)

    def read_named(self, base: int, offsets) -> np.ndarray:
        """The window's bytes at ``base + offsets`` (a device int32 array)."""
        return self.nic.read_named(self.state, base, offsets)

    def write_expect(self, idx: int, msg_id: int) -> None:
        """Host MMIO write into the NIC's expected-msg_id slot table."""
        self.state = self.nic.write_expect(self.state, idx, msg_id)

    def snapshot(self) -> dict:
        # NIC step donates its input state: snapshots must own their buffers
        return dict(nic=jax.tree.map(jnp.copy, self.state),
                    engines=[e.snapshot() for e in self.engines],
                    completions=list(self.completions))

    def restore(self, snap: dict) -> None:
        self.state = jax.tree.map(jnp.copy, snap["nic"])
        for e, s in zip(self.engines, snap["engines"]):
            e.restore(s)
        self.completions = list(snap["completions"])
