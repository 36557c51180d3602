"""Host milliseconds of collective plan steps per fabric tick.

Self time of the program's ``mpi.plan`` spans in the traced window
(``bench/spans.py``): advancing a collective's state machine when one of its
requests completes, the host's reductions included, divided by the fabric
ticks of the window.  None where the window holds no program span.
"""
from bench.spans import PLAN, per_tick_ms


def read(run):
    return per_tick_ms(run, PLAN)
