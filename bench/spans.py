"""The program's own host spans in a traced window, and what they cover.

The program wraps each host site of the fabric tick in a named
``jax.profiler.TraceAnnotation`` (``<layer>.<site>``).  The names are
written out here rather than imported from the program, so that a rename
there leaves these readers reading nothing instead of something else.

A span's self time is its duration less the part of it that program spans
nested inside it cover; runtime annotations (``PjitFunction``,
``np.asarray(jax.Array)``) are not spans of the program and are not
subtracted.  Spans on one thread nest, so one sort and one stack give every
span's parent and every stretch's innermost span.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from bench.trace import Event

PROGRAM_SPANS = frozenset((
    "link.pop", "link.push", "nic.step", "nic.write",
    "d2h.ingress", "d2h.to_host", "d2h.egress", "d2h.completions",
    "d2h.host_window", "d2h.link_stats",
    "engine.poll", "engine.frames", "engine.completions",
    "mpi.plan",
    "fabric.route", "fabric.pack",
))
D2H = frozenset(n for n in PROGRAM_SPANS if n.startswith("d2h."))
DISPATCH = frozenset(("link.pop", "link.push", "nic.step", "nic.write"))
ENGINE = frozenset(n for n in PROGRAM_SPANS if n.startswith("engine."))
PLAN = frozenset(("mpi.plan",))
FABRIC = frozenset(("fabric.route", "fabric.pack"))
NONE = "none"


def program_spans(trace) -> List[Event]:
    """The events of the window's host thread that are program spans,
    clipped to the window, in order of start (an enclosing span first)."""
    lo, hi = trace.window.start_ns, trace.window.end_ns
    spans = [Event(ev.name, max(ev.start_ns, lo), min(ev.end_ns, hi))
             for ev in trace.host if ev.name in PROGRAM_SPANS]
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


def self_seconds(trace, names: Iterable[str]) -> Dict[str, float]:
    """Self seconds of the spans of each name in ``names``, summed."""
    spans = program_spans(trace)
    own = [ev.end_ns - ev.start_ns for ev in spans]
    for i, p in enumerate(_parents(spans)):
        if p >= 0:
            own[p] -= spans[i].end_ns - spans[i].start_ns
    out = {name: 0.0 for name in names}
    for ev, ns in zip(spans, own):
        if ev.name in out:
            out[ev.name] += ns / 1e9
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
    """Self milliseconds of the spans in ``names`` per fabric tick; None
    where the window holds no program span at all."""
    if run.trace is None or not run.ticks or not program_spans(run.trace):
        return None
    return 1e3 * sum(self_seconds(run.trace, names).values()) / run.ticks
