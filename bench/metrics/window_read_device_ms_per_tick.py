"""Device milliseconds of the named-bytes FIN read per fabric tick.

Sums the device durations of ``SpinNIC.read_named``'s gather
(``core/spin_nic.py`` ``_read_named``, the ``XLA Modules`` events named
``jit__read_named(<id>)``) in the traced window: the read, at a rendezvous
FIN, of only the bytes a datatype with holes names.  None where the window
ran no such read (a program without it, or a cell whose types are dense).
"""
import re

from bench.trace import program_name

READ_NAMED = re.compile(r"^jit__read_named$")


def read(run):
    if run.trace is None or not run.ticks:
        return None
    secs = [ev.seconds for dev in run.trace.modules for ev in dev
            if READ_NAMED.match(program_name(ev.name))]
    if not secs:
        return None
    return 1e3 * sum(secs) / run.ticks
