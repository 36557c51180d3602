"""The program's own host spans in a traced window, and what they cover.

The program wraps each host site of the fabric tick in a named
``jax.profiler.TraceAnnotation`` (``<layer>.<site>``).  A host event is a
program span when its name has that form and its layer is one of the
program's (``SPAN``), so a site the program adds to a known layer is read
without an edit here.  The layers are written out here rather than
imported from the program, so that a renamed layer leaves these readers
reading nothing instead of something else.  The harness's own spans
(``window``, ``tick``, ...) have no layer.

A span's self time is its duration less the part of it that program spans
nested inside it cover; runtime annotations (``PjitFunction``,
``np.asarray(jax.Array)``) are not spans of the program and are not
subtracted.  Spans on one thread nest, so one sort and one stack give every
span's parent and every stretch's innermost span.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

from bench.trace import Event

SPAN = re.compile(r"^(link|nic|d2h|engine|mpi|fabric)\.[a-z_]+$")
# the catalogue's sites today, which the program's own test holds its
# catalogue to; the readers match by ``SPAN`` and the layers below
PROGRAM_SPANS = frozenset((
    "link.pop", "link.push", "nic.step", "nic.write",
    "d2h.ingress", "d2h.to_host", "d2h.egress", "d2h.completions",
    "d2h.host_window", "d2h.link_stats",
    "engine.poll", "engine.frames", "engine.completions",
    "mpi.plan",
    "fabric.route", "fabric.pack",
))


@dataclasses.dataclass(frozen=True)
class Layer:
    """Every program span of one layer: ``name in Layer("d2h")`` holds for
    each ``d2h.<site>``, those the program adds later included."""
    layer: str

    def __contains__(self, name: str) -> bool:
        return name.startswith(self.layer + ".")


D2H = Layer("d2h")
ENGINE = Layer("engine")
DISPATCH = frozenset(("link.pop", "link.push", "nic.step", "nic.write"))
PLAN = frozenset(("mpi.plan",))
FABRIC = frozenset(("fabric.route", "fabric.pack"))
NONE = "none"


def program_spans(trace) -> List[Event]:
    """The events of the window's host thread that are program spans,
    clipped to the window, in order of start (an enclosing span first)."""
    lo, hi = trace.window.start_ns, trace.window.end_ns
    spans = [Event(ev.name, max(ev.start_ns, lo), min(ev.end_ns, hi))
             for ev in trace.host if SPAN.match(ev.name)]
    spans.sort(key=lambda ev: (ev.start_ns, -ev.end_ns))
    return spans


def _parents(spans: List[Event]) -> List[int]:
    """Index of each span's innermost enclosing span, -1 for none."""
    out, stack = [], []
    for i, ev in enumerate(spans):
        while stack and spans[stack[-1]].end_ns <= ev.start_ns:
            stack.pop()
        out.append(stack[-1] if stack else -1)
        stack.append(i)
    return out


def self_seconds(trace) -> Dict[str, float]:
    """Self seconds of the program spans of each name in the window,
    summed."""
    spans = program_spans(trace)
    own = [ev.end_ns - ev.start_ns for ev in spans]
    for i, p in enumerate(_parents(spans)):
        if p >= 0:
            own[p] -= spans[i].end_ns - spans[i].start_ns
    out: Dict[str, float] = {}
    for ev, ns in zip(spans, own):
        out[ev.name] = out.get(ev.name, 0.0) + ns / 1e9
    return out


def _innermost(trace) -> List[Tuple[float, float, str]]:
    """The window cut into stretches, each labelled with the innermost
    program span the host was in, or ``none``."""
    spans = program_spans(trace)
    cuts: List[Tuple[float, float, str]] = []
    stack: List[Event] = []
    t = trace.window.start_ns

    def upto(end: float) -> None:
        nonlocal t
        if end > t:
            cuts.append((t, end, stack[-1].name if stack else NONE))
            t = end

    for ev in spans:
        while stack and stack[-1].end_ns <= ev.start_ns:
            upto(stack[-1].end_ns)
            stack.pop()
        upto(ev.start_ns)
        stack.append(ev)
    while stack:
        upto(stack[-1].end_ns)
        stack.pop()
    upto(trace.window.end_ns)
    return cuts


def idle_by_span(trace) -> Dict[str, float]:
    """Idle seconds of the first device in the window, by the innermost
    program span the host was in; ``none`` holds idle time outside every
    program span."""
    out: Dict[str, float] = {}
    cuts = _innermost(trace)
    j = 0
    for s, e in trace.idle_gaps():
        while j < len(cuts) and cuts[j][1] <= s:
            j += 1
        k = j
        while k < len(cuts) and cuts[k][0] < e:
            lo, hi = max(s, cuts[k][0]), min(e, cuts[k][1])
            if hi > lo:
                out[cuts[k][2]] = out.get(cuts[k][2], 0.0) + (hi - lo) / 1e9
            k += 1
    return out


def per_tick_ms(run, names) -> "float | None":
    """Self milliseconds of the spans whose names are ``in names`` (a set
    of names or a ``Layer``) per fabric tick; None where the window holds
    no program span at all."""
    if run.trace is None or not run.ticks or not program_spans(run.trace):
        return None
    return 1e3 * sum(s for name, s in self_seconds(run.trace).items()
                     if name in names) / run.ticks
