"""The program's span catalogue: named host spans on the fabric tick.

Each span is a ``jax.profiler.TraceAnnotation``.  With no profiler running
it records nothing and costs well under a microsecond; under
``jax.profiler.trace`` it lands on the calling thread's line of the
profiler's host plane, on the same clock as the device planes, so a device
idle gap can be pinned on the host site that caused it.

Names are ``<layer>.<site>`` and stable: readers of a trace match them
letter for letter.  Spans nest; a span's self time is its duration minus
the spans inside it.  Every blocking device→host read of the tick sits
inside exactly one ``d2h.*`` span, and each ``d2h.*`` span wraps one wait
for what one decision of the host needs.  Work done per node is spanned
per node, never around a loop over nodes; the one read of all launched
NIC steps (``d2h.to_host``) is a single wait, not a loop.

=====================  ===================================================
``link.pop``           dispatch of the link drain, with its argument puts
``link.push``          key split, upload and dispatch of the link admit
``nic.step``           dispatch of one node's NIC step
``nic.write``          dispatch of a small host write into ``NICState``
``d2h.ingress``        the host's wait for the delivered ingress batches
``d2h.to_host``        the one read a tick of the NIC-step outputs of every
                       node whose step was launched: host-path
                       (non-matching) frames, handler egress and the
                       completion counter FIFO
``d2h.egress``         retired from the program (the egress is read under
                       ``d2h.to_host``); kept for trace readers that match it
``d2h.completions``    ``SpinNIC.pop_counters``' read of a counter FIFO
``d2h.host_window``    an engine's read of the NIC's host DMA window
``d2h.link_stats``     read of the links' counters
``engine.poll``        a node's host engines polled: timers, retransmits,
                       sends
``engine.frames``      a node's engines handed host-path frames (ACKs,
                       control datagrams)
``engine.completions`` a node's engines handed drained completions
``mpi.plan``           one step of a collective plan, reductions included
``fabric.route``       MAC routing of one node's outbound frames
``fabric.pack``        packing the routed frames into the links' batch
=====================  ===================================================
"""
from __future__ import annotations

import jax

SPANS = (
    "link.pop", "link.push", "nic.step", "nic.write",
    "d2h.ingress", "d2h.to_host", "d2h.egress", "d2h.completions",
    "d2h.host_window", "d2h.link_stats",
    "engine.poll", "engine.frames", "engine.completions",
    "mpi.plan",
    "fabric.route", "fabric.pack",
)
_NAMES = frozenset(SPANS)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The host span ``name`` of the catalogue, as a context manager."""
    if name not in _NAMES:
        raise ValueError(f"{name!r} is not in the span catalogue")
    return jax.profiler.TraceAnnotation(name)
