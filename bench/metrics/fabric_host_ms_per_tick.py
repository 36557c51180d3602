"""Host milliseconds of the fabric's own work per fabric tick.

Self time of the program's ``fabric.route`` and ``fabric.pack`` spans in
the traced window (``bench/spans.py``): matching outbound frames' MAC
addresses to nodes and packing them into the links' batch, divided by the
fabric ticks of the window.  None where the window holds no program span.
"""
from bench.spans import FABRIC, per_tick_ms


def read(run):
    return per_tick_ms(run, FABRIC)
