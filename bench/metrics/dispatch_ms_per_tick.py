"""Host milliseconds spent dispatching device work per fabric tick.

Self time of the program's ``link.pop``, ``link.push``, ``nic.step`` and
``nic.write`` spans in the traced window (``bench/spans.py``): launching the
link drain and admit, each busy node's NIC step and the small writes into
NIC state, with their argument uploads, divided by the fabric ticks of the
window.  None where the window holds no program span.
"""
from bench.spans import DISPATCH, per_tick_ms


def read(run):
    return per_tick_ms(run, DISPATCH)
