"""Blocking device-to-host reads per fabric tick.

Counts the program's ``d2h.*`` spans in the traced window (``bench/spans.py``):
each wraps one wait of the host for what one of its decisions needs (the
delivered ingress, a node's host-path frames, egress or completion FIFO,
an engine's read of the DMA window, the link counters), divided by the
fabric ticks of the window.  None where the window holds no program span.
"""
from bench.spans import D2H, program_spans


def read(run):
    if run.trace is None or not run.ticks:
        return None
    spans = program_spans(run.trace)
    if not spans:
        return None
    return sum(ev.name in D2H for ev in spans) / run.ticks
