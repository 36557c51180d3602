"""The fabric: N nodes wired together through lossy links, MAC-routed.

Topology model: every node owns one *ingress link* (its wire).  A frame
leaving any node is routed by destination MAC onto the target node's
ingress link, where the link model applies loss / duplication / latency /
reordering; ``latency`` ticks later the frame surfaces in the target's
ingress batch.  One :meth:`Fabric.tick` advances every node by one NIC
step plus one link round — discrete-event at batch granularity, the same
granularity as ``SpinNIC.step``.

**Hot loop.** When every link shares one config and every node one batch
size (the common case — an MPI job, a benchmark sweep), the per-tick work
is batched across nodes: one vmapped ``pop`` drains all N links in a
single device call, destination MACs of all egress frames are matched
against the node-MAC matrix in one vectorized compare (no per-frame
``bytes()``/dict hops), and all routed traffic lands on the links through
one vmapped ``push``.  Every node whose link delivered frames has its
NIC step launched first (``Node.launch``), in node order; one
``device_get`` then reads the outputs of all of them (``fetch``), so one
read serves every busy node, and only then does each node, in order,
hand its frames and completions to its engines (``Node.tick``).  Nodes
whose link delivered nothing this tick skip the NIC step entirely
(``Node.tick_idle``) — on a mostly-idle fabric the tick cost is one pop,
N cheap engine polls, and at most one push.
Heterogeneous ``link_cfgs`` / batch sizes fall back to the per-link loop.

The whole system state (per-node ``NICState``, per-link ``LinkState``,
host-engine counters, the tick clock, the PRNG key) is captured by
:meth:`checkpoint` and restored by :meth:`restore` — a fabric run is a
pure function of (initial state, seed), like a single NIC.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import packet as pkt
from repro.net import link as linklib
from repro.net.node import Node, fetch


@functools.partial(jax.jit, static_argnums=(0, 1))
def _pop_all(cfg: linklib.LinkConfig, n: int, states, now):
    """Drain all N links at once: one device call instead of N."""
    return jax.vmap(lambda s: linklib._pop(s, now, n))(states)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _push_all(cfg: linklib.LinkConfig, states, keys, batch, now):
    """Admit per-node egress batches onto all N links in one device call
    (empty lanes carry ``valid=False`` rows and only consume PRNG)."""
    return jax.vmap(
        lambda s, k, b: linklib._push(cfg, s, k, b, now))(states, keys, batch)


class Fabric:
    def __init__(self, nodes: Sequence[Node],
                 link_cfg: linklib.LinkConfig = linklib.LinkConfig(),
                 link_cfgs: Optional[Sequence[linklib.LinkConfig]] = None,
                 seed: int = 0):
        """``link_cfgs`` (one per node, ingress side) overrides the shared
        ``link_cfg`` when per-node asymmetry is wanted."""
        self.nodes: List[Node] = list(nodes)
        cfgs = list(link_cfgs) if link_cfgs is not None else \
            [link_cfg] * len(self.nodes)
        assert len(cfgs) == len(self.nodes)
        self.links = [linklib.Link(c) for c in cfgs]
        self.key = jax.random.PRNGKey(seed)
        self.now = 0
        self.unroutable = 0
        self._by_mac: Dict[bytes, int] = {
            n.mac: i for i, n in enumerate(self.nodes)}
        # (N, 6) MAC matrix for the vectorized routing compare
        self._mac_mat = np.stack(
            [np.frombuffer(n.mac, np.uint8) for n in self.nodes])
        # uniform fast path: identical link cfgs + identical node batches
        self._uniform = (len(set(cfgs)) == 1
                         and len({n.batch for n in self.nodes}) == 1)
        if self._uniform:
            self._cfg0 = cfgs[0]
            self._batch0 = self.nodes[0].batch
            self._stack = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[l.init_state() for l in self.links])
            self.link_states = None
        else:
            self._stack = None
            self.link_states = [l.init_state() for l in self.links]

    # ---------------------------------------------------------------- tick
    def tick(self) -> None:
        if self._uniform:
            self._tick_batched()
        else:
            self._tick_loop()
        self.now += 1

    def _route(self, frames: List[np.ndarray],
               outbound: List[List[np.ndarray]]) -> None:
        """Vectorized MAC routing: match every frame's destination MAC
        against the node matrix in one compare."""
        if not frames:
            return
        with obs.span("fabric.route"):
            dst6 = np.stack([f[pkt.ETH_DST:pkt.ETH_DST + 6] for f in frames])
            hit = (dst6[:, None, :] == self._mac_mat[None, :, :]).all(-1)
            dest = hit.argmax(1)
            ok = hit.any(1)
            self.unroutable += int((~ok).sum())
            for i in np.flatnonzero(ok):
                outbound[dest[i]].append(frames[i])

    def _tick_batched(self) -> None:
        now = self.now
        with obs.span("link.pop"):
            self._stack, ing = _pop_all(self._cfg0, self._batch0,
                                        self._stack, now)
        # one host sync for the whole fabric: materialize the delivered
        # batches as numpy (a few tens of KB) — per-node numpy slices are
        # free, where N eager device slices would each pay a dispatch
        with obs.span("d2h.ingress"):
            valid = np.asarray(ing.valid)
        busy = valid.any(1)
        if busy.any():
            with obs.span("d2h.ingress"):
                data, length = np.asarray(ing.data), np.asarray(ing.length)
        # launch every busy node's NIC step, then read all their outputs
        # at once: the device runs the queued steps while the host
        # dispatches the next, and the one wait covers what is left
        ingress = {i: pkt.PacketBatch(data[i], length[i], valid[i])
                   for i in np.flatnonzero(busy).tolist()}
        for i, batch in ingress.items():
            self.nodes[i].launch(batch)
        if ingress:
            fetch([self.nodes[i] for i in ingress])
        outbound: List[List[np.ndarray]] = [[] for _ in self.nodes]
        for i, node in enumerate(self.nodes):
            if i in ingress:
                frames = node.tick(ingress[i], now)
            else:
                frames = node.tick_idle(now)
            self._route(frames, outbound)
        self._flush_outbound(outbound)

    def _flush_outbound(self, outbound: List[List[np.ndarray]]) -> None:
        """Admit routed per-node egress onto all links in one vmapped
        push (stacked to (N, P, MTU), P a power of two so the jitted push
        compiles O(log) shapes)."""
        counts = [len(o) for o in outbound]
        if not any(counts):
            return
        n_nodes = len(self.nodes)
        with obs.span("fabric.pack"):
            p = 1 << max(0, (max(counts) - 1).bit_length())
            data = np.zeros((n_nodes, p, pkt.MTU), np.uint8)
            length = np.zeros((n_nodes, p), np.int32)
            ok = np.zeros((n_nodes, p), bool)
            for j, frames in enumerate(outbound):
                for k, f in enumerate(frames):
                    data[j, k, :len(f)] = f
                    length[j, k] = len(f)
                    ok[j, k] = True
        with obs.span("link.push"):
            self.key, sub = jax.random.split(self.key)
            keys = jax.random.split(sub, n_nodes)
            self._stack = _push_all(
                self._cfg0, self._stack, keys,
                pkt.PacketBatch(jnp.asarray(data), jnp.asarray(length),
                                jnp.asarray(ok)), self.now)

    def _tick_loop(self) -> None:
        """Per-link fallback for heterogeneous link configs/batches."""
        now = self.now
        outbound: List[List[np.ndarray]] = [[] for _ in self.nodes]
        for i, node in enumerate(self.nodes):
            with obs.span("link.pop"):
                self.link_states[i], ingress = self.links[i].pop(
                    self.link_states[i], now, node.batch)
            frames = node.tick(ingress, now)
            self._route(frames, outbound)
        for j, frames in enumerate(outbound):
            if not frames:
                continue
            n = 1 << max(0, (len(frames) - 1).bit_length())
            with obs.span("link.push"):
                self.key, sub = jax.random.split(self.key)
                self.link_states[j] = self.links[j].push(
                    self.link_states[j], sub, pkt.stack_frames(frames, n=n),
                    now)

    def run(self, max_ticks: int = 10_000, until=None) -> int:
        """Tick until ``until()`` (default: every node's engines done and
        all links drained) or ``max_ticks``.  Returns ticks executed."""
        if until is None:
            def until():
                if not all(n.done for n in self.nodes):
                    return False
                if self._uniform:
                    return not bool(
                        np.asarray(self._stack.occupied).any())
                return not any(bool(np.asarray(s.occupied).any())
                               for s in self.link_states)
        t0 = self.now
        while self.now - t0 < max_ticks and not until():
            self.tick()
        return self.now - t0

    def reset(self, seed: int = 0) -> None:
        """Fresh links/clock/PRNG (node NIC states reset via Node.reset)."""
        if self._uniform:
            self._stack = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[l.init_state() for l in self.links])
        else:
            self.link_states = [l.init_state() for l in self.links]
        self.key = jax.random.PRNGKey(seed)
        self.now = 0
        self.unroutable = 0

    # ---------------------------------------------------------- observability
    def node(self, name: str) -> Node:
        return next(n for n in self.nodes if n.name == name)

    def _per_link_states(self) -> List[linklib.LinkState]:
        if self._uniform:
            return [jax.tree.map(lambda a, i=i: a[i], self._stack)
                    for i in range(len(self.nodes))]
        return self.link_states

    def link_stats(self) -> List[dict]:
        with obs.span("d2h.link_stats"):
            if self._uniform:
                # one transfer per counter for the whole fabric
                names = ("pushed", "lost", "overflowed", "duplicated",
                         "reordered", "delivered", "deferred")
                cols = {k: np.asarray(getattr(self._stack, k))
                        for k in names}
                return [{k: int(cols[k][i]) for k in names}
                        for i in range(len(self.nodes))]
            return [l.stats(s) for l, s in zip(self.links, self.link_states)]

    def stats(self) -> dict:
        """Fabric-wide health: unroutable frames (frames whose destination
        MAC matches no node — silently dropped by real switches, loudly
        counted here) plus per-link wire and stall counters."""
        links = self.link_stats()
        totals = {f"{k}_total": sum(l[k] for l in links)
                  for k in ("lost", "overflowed", "deferred", "delivered")}
        return dict(unroutable=self.unroutable, links=links, **totals)

    # ------------------------------------------------------------ checkpoint
    def checkpoint(self) -> dict:
        return dict(
            now=self.now,
            key=jnp.copy(self.key),
            unroutable=self.unroutable,
            links=[jax.tree.map(jnp.copy, s)
                   for s in self._per_link_states()],
            nodes=[n.snapshot() for n in self.nodes],
        )

    def restore(self, snap: dict) -> None:
        self.now = snap["now"]
        self.key = jnp.copy(snap["key"])
        self.unroutable = snap["unroutable"]
        if self._uniform:
            self._stack = jax.tree.map(
                lambda *xs: jnp.stack([jnp.copy(x) for x in xs]),
                *snap["links"])
        else:
            self.link_states = [jax.tree.map(jnp.copy, s)
                                for s in snap["links"]]
        for n, s in zip(self.nodes, snap["nodes"]):
            n.restore(s)
