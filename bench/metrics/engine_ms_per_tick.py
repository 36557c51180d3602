"""Host milliseconds of the host engines per fabric tick.

Self time of the program's ``engine.*`` spans in the traced window
(``bench/spans.py``; today ``engine.poll``, ``engine.frames`` and
``engine.completions``): the MPI and SLMP engines' timers, retransmits,
sends and frame and completion handling, less the device reads, NIC writes
and collective plan steps they call, divided by the fabric ticks of the
window.  None where the window holds no program span.
"""
from bench.spans import ENGINE, per_tick_ms


def read(run):
    return per_tick_ms(run, ENGINE)
