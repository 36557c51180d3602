"""Device milliseconds of the NIC step per fabric tick.

Sums the device durations of the jitted SpinNIC datapath
(``core/spin_nic.py`` ``SpinNIC._step_impl``, the ``XLA Modules`` events named
``jit__step_impl(<id>)``) in the traced window.  The host's drain of the
completion FIFO (``pop_counters``) runs as small generic programs
(``jit_dynamic_slice``, ``jit_scatter``) that the trace cannot tell from
other reads of NIC state; they are counted by ``device_calls_per_tick`` only.
"""
import re

from bench.trace import program_name

NIC_STEP = re.compile(r"^jit__step_impl$")


def read(run):
    if run.trace is None or not run.ticks:
        return None
    secs = [ev.seconds for dev in run.trace.modules for ev in dev
            if NIC_STEP.match(program_name(ev.name))]
    if not secs:
        return None
    return 1e3 * sum(secs) / run.ticks
