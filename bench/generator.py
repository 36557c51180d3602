"""The one traffic generator: builds a cell's communicator from its
configuration file and posts the operations its traffic mix names.

A configuration (``bench/configs/<name>.json``) gives the ranks, the
messaging settings (``repro.mpi.MpiConfig`` fields), the link settings
(``repro.net.LinkConfig`` fields but the loss) and the datatypes committed
on the NICs.  A traffic mix (``bench/traffic/<name>.json``) gives the
operation (``op``) and its sizes, the loss rate on every link and the seed of
the links' loss process (``loss_seed``), the largest burst of frames one node
may be handed in a tick (``warm_frames``), and the limits of the numbers
compared.  The operation is the cell's two modules (``bench/spec.py``): its
reference draws the inputs (``check.draw``), its program side posts the
requests and collects the outputs (``op.post``, ``op.outputs``); what every
operation shares, the counters and the modelled statistics, is here.

Every input comes from the seed: operation ``i`` of a run draws from
``default_rng([seed, 2, i])`` and the warm-up operation from
``default_rng([seed, 3])``.  Which frames the links lose is the mix's own
(``loss_seed``), the same for every seed: the values carried never steer the
simulation, so every seed makes the fabric do the same work, and the
modelled statistics of a cell's operations repeat from run to run.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from repro import mpi
from repro.core import ddt as ddtlib
from repro.core import packet as pkt
from repro.net import Fabric, LinkConfig, Node
from repro.net.node import HostEngine

_PRIMITIVES = {"float32": ddtlib.MPI_FLOAT, "float64": ddtlib.MPI_DOUBLE,
               "int32": ddtlib.MPI_INT, "byte": ddtlib.MPI_BYTE}
_ENGINE_KEYS = ("bytes_sent", "eager_sent", "rdv_sent", "retransmits")
_LINK_KEYS = ("pushed", "lost", "delivered")
# the discard port: a frame to it matches no NIC ruleset and goes to the host
_DISCARD_PORT = 9


def to_ddt(spec) -> ddtlib.DDT:
    """The simulator's datatype object for a configuration's type spec."""
    if isinstance(spec, str):
        return _PRIMITIVES[spec]
    (kind, a), = spec.items()
    base = to_ddt(a["base"])
    if kind == "vector":
        return ddtlib.Vector(a["count"], a["blocklen"], a["stride"], base)
    if kind == "hvector":
        return ddtlib.HVector(a["count"], a["blocklen"], a["stride_bytes"],
                              base)
    if kind == "contiguous":
        return ddtlib.Contiguous(a["count"], base)
    raise ValueError(f"unknown datatype constructor {kind!r}")


def build(cell, seed: int) -> "Traffic":
    """The communicator of ``cell`` (a ``spec.Cell``) and its traffic from
    ``seed``."""
    config, mix = cell.config, cell.mix
    reg = mpi.DatatypeRegistry()
    ids = {d["name"]: reg.register(to_ddt(d["type"]), count=d["count"],
                                   name=d["name"])
           for d in config["datatypes"]}
    link = LinkConfig(loss=mix["loss"], **config["link"])
    comm = mpi.Communicator(config["ranks"], registry=reg, link_cfg=link,
                            seed=mix["loss_seed"],
                            cfg=mpi.MpiConfig(**config["mpi"]))
    return Traffic(comm, cell, seed, ids)


@dataclasses.dataclass
class Done:
    """One operation that completed: its inputs, its outputs as they were
    at completion, and the modelled statistics of its span of ticks."""
    index: int
    inputs: object
    outputs: object
    error: Optional[str]
    modelled: dict


class Op:
    """One posted operation and the counters at the moment it was posted."""

    def __init__(self, traffic: "Traffic", index: int, rng):
        self.t = traffic
        self.index = index
        comm = traffic.comm
        self.tick0 = comm.now
        self.eng0 = _engine_totals(comm)
        self.link0 = _link_totals(comm)
        cell = traffic.cell
        self.inputs = cell.check.draw(cell.mix, cell.config, rng)
        self.posted = cell.op.post(comm, cell.mix, traffic.ids, self.inputs)
        self.reqs = self.posted[0]

    def error(self) -> Optional[str]:
        errs = [r.error for r in self.reqs if r.error]
        errs += [e for eng in self.t.comm.engines for e in eng.errors]
        return "; ".join(errs) or None

    def finished(self) -> bool:
        return all(r.done for r in self.reqs) or self.error() is not None

    def complete(self) -> Done:
        comm = self.t.comm
        outputs = self.t.cell.op.outputs(self.posted)
        eng, link = _engine_totals(comm), _link_totals(comm)
        modelled = dict(
            ticks=comm.now - self.tick0,
            bytes_wire=eng["bytes_sent"] - self.eng0["bytes_sent"],
            msgs_total=(eng["eager_sent"] + eng["rdv_sent"]
                        - self.eng0["eager_sent"] - self.eng0["rdv_sent"]),
            retransmits=eng["retransmits"] - self.eng0["retransmits"],
            **{f"frames_{k}": link[k] - self.link0[k] for k in _LINK_KEYS})
        return Done(self.index, self.inputs, outputs, self.error(), modelled)


class Traffic:
    def __init__(self, comm, cell, seed: int, ids: dict):
        self.comm = comm
        self.cell = cell
        self.seed = seed
        self.ids = ids

    def post(self, index: int) -> Op:
        return Op(self, index, np.random.default_rng([self.seed, 2, index]))

    def warm_up(self, max_ticks: int = 1_000_000) -> int:
        """Compile what the window will run: a burst of every padded size of
        frames one node can be handed in a tick, on a throwaway fabric that
        shares this communicator's NIC and link settings, then one
        operation of the cell's own traffic from a seed the window never
        uses.  Returns the operation's ticks."""
        self._warm_bursts()
        op = Op(self, -1, np.random.default_rng([self.seed, 3]))
        t0 = self.comm.now
        while not op.finished():
            if self.comm.now - t0 >= max_ticks:
                raise RuntimeError("warm-up operation did not complete")
            self.comm.progress(1)
        if op.error():
            raise RuntimeError(f"warm-up operation failed: {op.error()}")
        return self.comm.now - t0

    def _warm_bursts(self) -> None:
        comm = self.comm
        n = comm.n_ranks
        burst = _Burst(pkt.node_mac(0), pkt.node_mac(n - 1 if n > 1 else 0))
        nodes = [Node(f"warm{r}", pkt.node_mac(r), nic=comm.nic,
                      engines=[burst] if r == 0 else [])
                 for r in range(n)]
        fab = Fabric(nodes, link_cfg=comm.link_cfg)
        size = 1
        while size <= self.cell.mix["warm_frames"]:
            burst.count = size
            fab.tick()
            size *= 2


class _Burst(HostEngine):
    """Puts ``count`` frames for one peer on the wire at the next poll."""

    def __init__(self, src: bytes, dst: bytes):
        self.src, self.dst = src, dst
        self.count = 0

    def poll(self, now: int) -> List[np.ndarray]:
        frames = [pkt.make_udp(np.zeros(8, np.uint8), dport=_DISCARD_PORT,
                               src_mac=self.src, dst_mac=self.dst)
                  for _ in range(self.count)]
        self.count = 0
        return frames


def _engine_totals(comm) -> dict:
    stats = comm.stats()
    return {k: sum(s.get(k, 0) for s in stats) for k in _ENGINE_KEYS}


def _link_totals(comm) -> dict:
    links = comm.link_stats()
    return {k: sum(ln.get(k, 0) for ln in links) for k in _LINK_KEYS}
