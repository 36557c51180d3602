"""The fabric: N nodes wired together through lossy links, MAC-routed.

Topology model: every node owns one *ingress link* (its wire).  A frame
leaving any node is routed by destination MAC onto the target node's
ingress link, where the link model applies loss / duplication / latency /
reordering; ``latency`` ticks later the frame surfaces in the target's
ingress batch.  One :meth:`Fabric.tick` advances every node by one NIC
step plus one link round — discrete-event at batch granularity, the same
granularity as ``SpinNIC.step``.

**Hot loop.** Every link shares one config and every node one batch
size, so the per-tick work is batched across nodes: one vmapped ``pop``
drains all N links in a single device call, destination MACs of all
egress frames are matched against the node-MAC matrix in one vectorized
compare (no per-frame ``bytes()``/dict hops), and all routed traffic
lands on the links through one vmapped ``push``.  Every node whose link
delivered frames has its NIC step launched first (``Node.launch``), in
node order; one ``device_get`` then reads the outputs of all of them
(``fetch``), so one read serves every busy node, and only then does each
node, in order, hand its frames and completions to its engines
(``Node.tick``).  Nodes whose link delivered nothing this tick skip the
NIC step entirely (``Node.tick_idle``) — on a mostly-idle fabric the tick
cost is one pop, N cheap engine polls, and at most one push.

The whole system state (per-node ``NICState``, per-link ``LinkState``,
host-engine counters, the tick clock, the PRNG key) is captured by
:meth:`checkpoint` and restored by :meth:`restore` — a fabric run is a
pure function of (initial state, seed), like a single NIC.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import packet as pkt
from repro.net import link as linklib
from repro.net.node import Node, fetch


@functools.partial(jax.jit, static_argnums=(0,))
def _pop_all(n: int, states, now):
    """Drain all N links at once: one device call instead of N."""
    return jax.vmap(lambda s: linklib._pop(s, now, n))(states)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _push_all(cfg: linklib.LinkConfig, states, keys, batch, now):
    """Admit per-node egress batches onto all N links in one device call
    (empty lanes carry ``valid=False`` rows and only consume PRNG)."""
    return jax.vmap(
        lambda s, k, b: linklib._push(cfg, s, k, b, now))(states, keys, batch)


def _stacked(states: Sequence[linklib.LinkState]) -> linklib.LinkState:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


class Fabric:
    def __init__(self, nodes: Sequence[Node],
                 link_cfg: linklib.LinkConfig = linklib.LinkConfig(),
                 seed: int = 0):
        """Every node's ingress link runs ``link_cfg``; every node must
        step the same ``batch`` of frames a tick."""
        self.nodes: List[Node] = list(nodes)
        batches = sorted({n.batch for n in self.nodes})
        if len(batches) != 1:
            raise ValueError(
                f"fabric nodes must share one batch size, got {batches}")
        self.link_cfg = link_cfg
        self.batch = batches[0]
        self.key = jax.random.PRNGKey(seed)
        self.now = 0
        self.unroutable = 0
        # (N, 6) MAC matrix for the vectorized routing compare
        self._mac_mat = np.stack(
            [np.frombuffer(n.mac, np.uint8) for n in self.nodes])
        # every link's state, stacked on a leading node axis
        self._stack = _stacked(
            [linklib.make_state(link_cfg.capacity)] * len(self.nodes))

    # ---------------------------------------------------------------- tick
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

    def tick(self) -> None:
        now = self.now
        with obs.span("link.pop"):
            self._stack, ing = _pop_all(self.batch, self._stack, now)
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
        self.now += 1

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
                self.link_cfg, self._stack, keys,
                pkt.PacketBatch(jnp.asarray(data), jnp.asarray(length),
                                jnp.asarray(ok)), self.now)

    def run(self, max_ticks: int = 10_000, until=None) -> int:
        """Tick until ``until()`` (default: every node's engines done and
        all links drained) or ``max_ticks``.  Returns ticks executed."""
        if until is None:
            def until():
                return all(n.done for n in self.nodes) and not bool(
                    np.asarray(self._stack.occupied).any())
        t0 = self.now
        while self.now - t0 < max_ticks and not until():
            self.tick()
        return self.now - t0

    # ---------------------------------------------------------- observability
    def node(self, name: str) -> Node:
        return next(n for n in self.nodes if n.name == name)

    def link_stats(self) -> List[dict]:
        with obs.span("d2h.link_stats"):
            # one transfer per counter for the whole fabric
            names = ("pushed", "lost", "overflowed", "duplicated",
                     "reordered", "delivered", "deferred")
            cols = {k: np.asarray(getattr(self._stack, k)) for k in names}
            return [{k: int(cols[k][i]) for k in names}
                    for i in range(len(self.nodes))]

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
            links=[jax.tree.map(lambda a, i=i: a[i], self._stack)
                   for i in range(len(self.nodes))],
            nodes=[n.snapshot() for n in self.nodes],
        )

    def restore(self, snap: dict) -> None:
        self.now = snap["now"]
        self.key = jnp.copy(snap["key"])
        self.unroutable = snap["unroutable"]
        self._stack = _stacked(snap["links"])
        for n, s in zip(self.nodes, snap["nodes"]):
            n.restore(s)
