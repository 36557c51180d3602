"""The complete FPsPIN datapath (paper Fig 5) as one jitted device program.

One ``step`` processes a batch of ingress frames through the exact module
sequence of the hardware:

  1. ``pspin_pkt_match``   — execution-context matching (kernels/matcher);
                              non-matching frames are *forwarded to the
                              Corundum/host datapath* (returned unmodified).
  2. ``pspin_pkt_alloc``   — bimodal slot allocation in the L2 packet
                              buffer (core/alloc); on FIFO underflow the
                              frame is dropped and counted.
  3. ``pspin_ingress_dma`` — frames are DMA'd into the modelled L2 packet
                              buffer (a real (512 KiB,) uint8 array — the
                              handlers read their packet bytes back out of
                              it, like HPUs reading L1/L2).
  4. ``pspin_her_gen``     — HER generation + MPQ scheduling (core/her).
  5. handler execution     — header → packet → tail phases (core/handlers),
                              message state visible across phases.
  6. effect application    — ``pspin_egress_dma`` (handler sends are
                              arbitrated into one egress batch),
                              ``pspin_hostmem_dma`` (byte-granular,
                              unaligned-capable scatter into host memory),
                              counter FIFOs, completion notifications
                              (slot free).

Everything is a pure function of ``NICState`` — checkpointable, jittable,
and shardable (the packet axis shards over the data mesh axis).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import alloc as palloc
from repro.core import handlers as H
from repro.core import her as herlib
from repro.core import matching
from repro.core import packet as pkt


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NICState:
    l2: jax.Array            # (L2_PKT_BYTES,) uint8 packet buffer
    alloc: palloc.AllocState
    mpq: herlib.MPQState
    msg_state: jax.Array     # (MPQ, MSG_STATE_DIM) int32
    host: jax.Array          # (HOST,) uint8 — host DMA window
    counters: jax.Array      # (Q, QLEN) int32
    counter_count: jax.Array  # (Q,) int32
    cycles: jax.Array        # () int32
    dropped: jax.Array       # () int32 — alloc-failure drops
    expect: jax.Array        # (E,) uint32 — host-programmed per-slot
    #                          expected msg_id (0 = slot disarmed); the
    #                          MMIO analogue of posting a receive to the
    #                          NIC before granting the sender a CTS

    def tree_flatten(self):
        return (self.l2, self.alloc, self.mpq, self.msg_state, self.host,
                self.counters, self.counter_count, self.cycles,
                self.dropped, self.expect), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _select_out(acc: H.HandlerOut, new: H.HandlerOut, mask) -> H.HandlerOut:
    m1 = mask[:, None]
    return H.HandlerOut(
        egress_data=jnp.where(m1, new.egress_data, acc.egress_data),
        egress_len=jnp.where(mask, new.egress_len, acc.egress_len),
        egress_valid=jnp.where(mask, new.egress_valid, acc.egress_valid),
        dma_off=jnp.where(m1, new.dma_off, acc.dma_off),
        dma_val=jnp.where(m1, new.dma_val, acc.dma_val),
        state_delta=jnp.where(m1, new.state_delta, acc.state_delta),
        counter_queue=jnp.where(mask, new.counter_queue, acc.counter_queue),
        counter_val=jnp.where(mask, new.counter_val, acc.counter_val),
    )


class SpinNIC:
    """Host-side object holding installed execution contexts (fpspin_init)."""

    def __init__(self, contexts: List[H.ExecutionContext],
                 host_bytes: int = 1 << 20, batch: int = 64,
                 use_kernels: bool = False,
                 mpq_entries: int = herlib.MPQ_ENTRIES):
        assert len(contexts) >= 1
        self.contexts = contexts
        self.host_bytes = host_bytes
        self.batch = batch
        self.use_kernels = use_kernels
        self.mpq_entries = mpq_entries
        self.tables = matching.MatchTables.build(
            [c.ruleset for c in contexts])
        # the expect table currently has a single flat slot space indexed
        # from 0: exactly one context may own it (per-context base offsets
        # would be needed for more — assert rather than silently alias)
        assert sum(1 for c in contexts if c.n_expect > 0) <= 1, \
            "only one execution context may use the expect table"
        self._msgful = jnp.asarray(
            np.array([c.message_mode for c in contexts], bool))
        self._host_base = jnp.asarray(
            np.array([c.host_base for c in contexts], np.int32))
        self._step = jax.jit(self._step_impl, donate_argnums=(0,))

    # -------------------------------------------------------------- state
    def init_state(self) -> NICState:
        return NICState(
            l2=jnp.zeros((palloc.L2_PKT_BYTES,), jnp.uint8),
            alloc=palloc.make_state(),
            mpq=herlib.make_mpq(self.mpq_entries),
            msg_state=jnp.zeros((self.mpq_entries, H.MSG_STATE_DIM),
                                jnp.int32),
            host=jnp.zeros((self.host_bytes,), jnp.uint8),
            counters=jnp.zeros((H.N_COUNTER_QUEUES, H.COUNTER_QUEUE_LEN),
                               jnp.int32),
            counter_count=jnp.zeros((H.N_COUNTER_QUEUES,), jnp.int32),
            cycles=jnp.zeros((), jnp.int32),
            dropped=jnp.zeros((), jnp.int32),
            expect=jnp.zeros(
                (max(1, sum(c.n_expect for c in self.contexts)),),
                jnp.uint32),
        )

    # --------------------------------------------------------------- step
    def step(self, state: NICState, batch: pkt.PacketBatch
             ) -> Tuple[NICState, pkt.PacketBatch, pkt.PacketBatch]:
        """Process one ingress batch.

        Returns (state, egress_batch, to_host_batch): egress = handler
        sends; to_host = non-matching frames forwarded to the standard NIC
        datapath (ARP passthrough & friends, paper §IV).
        """
        return self._step(state, batch)

    def _step_impl(self, state: NICState, batch: pkt.PacketBatch):
        n = batch.n
        byte_iota = jnp.arange(pkt.MTU, dtype=jnp.int32)

        # (1) matching engine
        ctx_id, eom = matching.match_batch(batch, self.tables,
                                           use_kernel=self.use_kernels)
        process = batch.valid & (ctx_id >= 0)
        to_host = pkt.PacketBatch(batch.data, batch.length,
                                  batch.valid & (ctx_id < 0))

        # (2) allocator
        alloc_state, addr, ok = palloc.alloc(state.alloc, batch.length,
                                             process)
        dropped = state.dropped + (process & ~ok).sum().astype(jnp.int32)
        live = process & ok

        # (3) ingress DMA into the L2 packet buffer.  Frames land at
        # contiguous slot addresses, so this is a masked read-modify-write
        # of one MTU window per lane (dynamic_update_slice), not a
        # per-byte scatter — XLA:CPU executes scatters element-by-element,
        # and this loop is ~10x cheaper than the equivalent flat scatter.
        # Slot geometry guarantees addr + MTU <= L2_PKT_BYTES (large slots
        # are MTU-sized and the region ends on a slot boundary).
        def _dma_in(i, l2):
            a = addr[i]
            window = jax.lax.dynamic_slice(l2, (a,), (pkt.MTU,))
            keep = live[i] & (byte_iota < batch.length[i])
            return jax.lax.dynamic_update_slice(
                l2, jnp.where(keep, batch.data[i], window), (a,))

        l2 = jax.lax.cond(
            live.any(),
            lambda l2: jax.lax.fori_loop(0, n, _dma_in, l2),
            lambda l2: l2, state.l2)

        # (4) HER generation + scheduling (message-mode contexts only track
        #     MPQ state; packet-mode contexts always run packet handlers)
        msgful = self._msgful[jnp.maximum(ctx_id, 0)] & live
        msg_id = pkt.read_u32(batch.data, pkt.SLMP_MSGID)
        mpq, her = herlib.generate(state.mpq, ctx_id, addr, batch.length,
                                   msg_id, eom & msgful, msgful)
        run_header = her.run_header & msgful
        run_tail = her.run_tail & msgful

        # (5) handler execution: read packet bytes back from L2
        gather_off = jnp.where(
            live[:, None], addr[:, None] + byte_iota[None, :], 0)
        pkt_view = jnp.where(live[:, None], l2[gather_off], 0)

        def make_args(msg_state):
            return H.HandlerArgs(
                pkt=pkt_view, pkt_len=batch.length, msg_id=msg_id,
                eom=eom, ctx=ctx_id,
                msg_state=msg_state[her.slot],
                cycles=jnp.broadcast_to(state.cycles, (n,)),
                expect=state.expect)

        msg_state = state.msg_state
        phase_outs = []
        for phase, phase_mask in (("header", run_header),
                                  ("packet", live),
                                  ("tail", run_tail)):
            args = make_args(msg_state)
            acc = jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                               H.none_out())
            for c, ectx in enumerate(self.contexts):
                fn = getattr(ectx, phase)
                if fn is H.default_handler:
                    continue
                mask = phase_mask & (ctx_id == c)
                out = H.run_phase(fn, args, ectx.user, mask)
                acc = _select_out(acc, out, mask)
            # message state becomes visible to the next phase
            msg_state = msg_state.at[her.slot].add(
                jnp.where(phase_mask[:, None], acc.state_delta, 0))
            phase_outs.append(acc)

        # (6a) host DMA: byte-granular scatter (unaligned-capable).  Each
        # phase's scatter runs under a cond so phases that DMA'd nothing
        # this batch (header/tail on most traffic, every phase on ACK-only
        # batches) skip the expensive CPU scatter entirely.
        host = state.host
        base = self._host_base[jnp.maximum(ctx_id, 0)]
        for out in phase_outs:
            off = jnp.where(out.dma_off >= 0,
                            base[:, None] + out.dma_off,
                            self.host_bytes)           # OOB -> dropped
            host = jax.lax.cond(
                (out.dma_off >= 0).any(),
                lambda h, o=off, v=out.dma_val: h.at[o.reshape(-1)].set(
                    v.reshape(-1), mode="drop"),
                lambda h: h, host)

        # (6b) egress arbitration (axis_arb_mux): compact all sends
        eg_data = jnp.concatenate([o.egress_data for o in phase_outs])
        eg_len = jnp.concatenate([o.egress_len for o in phase_outs])
        eg_valid = jnp.concatenate([o.egress_valid for o in phase_outs])
        order = jnp.argsort(~eg_valid, stable=True)[:n]
        egress = pkt.PacketBatch(eg_data[order], eg_len[order],
                                 eg_valid[order])

        # (6c) counter FIFOs (cond-gated: most phases push no counters)
        counters, counter_count = state.counters, state.counter_count
        for out in phase_outs:
            def _push_counters(cc, out=out):
                counters, counter_count = cc
                for q in range(H.N_COUNTER_QUEUES):
                    sel = out.counter_queue == q
                    rank = jnp.cumsum(sel.astype(jnp.int32)) - 1
                    pos = jnp.where(sel,
                                    (counter_count[q] + rank)
                                    % H.COUNTER_QUEUE_LEN,
                                    H.COUNTER_QUEUE_LEN)
                    counters = counters.at[q, pos].set(out.counter_val,
                                                       mode="drop")
                    counter_count = counter_count.at[q].add(
                        sel.sum().astype(jnp.int32))
                return counters, counter_count

            counters, counter_count = jax.lax.cond(
                (out.counter_queue >= 0).any(), _push_counters,
                lambda cc: cc, (counters, counter_count))

        # (6d) completion notification -> free packet-buffer slots
        alloc_state = palloc.free(alloc_state, addr, live)

        new_state = NICState(
            l2=l2, alloc=alloc_state, mpq=mpq, msg_state=msg_state,
            host=host, counters=counters, counter_count=counter_count,
            cycles=state.cycles + 1, dropped=dropped, expect=state.expect)
        return new_state, egress, to_host

    # ------------------------------------------------------------- host API
    def write_expect(self, state: NICState, idx: int,
                     msg_id: int) -> NICState:
        """Host MMIO: arm (or disarm, msg_id=0) one slot of the expected
        msg_id table — the host posts the receive to the NIC *before*
        telling the sender to fire, so a recycled DMA region only accepts
        frames of its current occupant."""
        with obs.span("nic.write"):
            return dataclasses.replace(state, expect=_write_expect(
                state.expect, np.array([idx, msg_id], np.uint32)))

    def read_host(self, state: NICState, base: int, nbytes: int
                  ) -> np.ndarray:
        """Host read of the DMA window (the /dev/pspin0 mmap view)."""
        with obs.span("d2h.host_window"):
            return np.asarray(state.host[base:base + nbytes])

    def read_named(self, state: NICState, base: int, offsets: jax.Array
                   ) -> np.ndarray:
        """Host read of the window's bytes at ``base + offsets`` alone: one
        device gather, so a receive whose datatype spans far more bytes
        than it names reads what it names and not its extent."""
        with obs.span("d2h.host_window"):
            return np.asarray(_read_named(state.host, offsets,
                                          np.int32(base)))

    def pop_counters(self, state: NICState, queue: int
                     ) -> Tuple[np.ndarray, NICState]:
        """Drain a counter FIFO (host side).

        Returns ``(values, state)`` where the returned state has the queue
        count cleared — a second pop yields nothing until handlers push
        again (a real FIFO drain, not a peek).
        """
        with obs.span("d2h.completions"):
            cnt = int(state.counter_count[queue])
        if cnt == 0:
            # nothing pushed since the last drain: skip the device
            # round-trips (this runs after every non-idle fabric tick)
            return np.zeros(0, np.int32), state
        with obs.span("d2h.completions"):
            ring = np.asarray(state.counters[queue])
        return drained(ring, cnt), self.clear_counters(state, queue)

    def clear_counters(self, state: NICState, queue: int) -> NICState:
        """Host MMIO: reset a counter FIFO's count once it is drained."""
        with obs.span("nic.write"):
            return dataclasses.replace(state, counter_count=_clear_counters(
                state.counter_count, queue))


# Host writes into NIC state.  Each is one compiled program: run eagerly,
# an ``.at[].set`` launches five small programs and uploads twice.  Neither
# donates its input, so a caller may keep the state it wrote into.
@jax.jit
def _write_expect(expect: jax.Array, idx_msg: jax.Array) -> jax.Array:
    """``expect`` with slot ``idx_msg[0]`` set to ``idx_msg[1]``."""
    return expect.at[idx_msg[0]].set(idx_msg[1])


@functools.partial(jax.jit, static_argnums=1)
def _clear_counters(counter_count: jax.Array, queue: int) -> jax.Array:
    return counter_count.at[queue].set(0)


@jax.jit
def _read_named(host: jax.Array, offsets: jax.Array,
                base: jax.Array) -> jax.Array:
    """``host[base + offsets]``: the bytes a datatype names in one slot."""
    return jnp.take(host, base + offsets)


def drained(ring: np.ndarray, count: int) -> np.ndarray:
    """The values a drain of one counter FIFO returns, oldest first.

    ``ring`` is a host copy of the queue's ``(COUNTER_QUEUE_LEN,)`` ring
    and ``count`` the pushes since the last drain; past the ring's length
    the oldest entries were overwritten and are lost.
    """
    start = max(0, count - H.COUNTER_QUEUE_LEN)
    idx = np.arange(start, count) % H.COUNTER_QUEUE_LEN
    return ring[idx].astype(np.int32)
