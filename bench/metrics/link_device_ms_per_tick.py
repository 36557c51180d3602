"""Device milliseconds of the link model per fabric tick.

Sums the device durations of the link programs in the traced window: the
vmapped pop and push over all links (``net/fabric.py`` ``_pop_all`` and
``_push_all``) and, on fabrics whose links differ, the per-link ``_pop`` and
``_push`` of ``net/link.py``.  In the trace they are the ``XLA Modules``
events named ``jit__pop_all(<id>)``, ``jit__push_all(<id>)``,
``jit__pop(<id>)`` and ``jit__push(<id>)``.
"""
import re

from bench.trace import program_name

LINK = re.compile(r"^jit__(pop|push)(_all)?$")


def read(run):
    if run.trace is None or not run.ticks:
        return None
    secs = [ev.seconds for dev in run.trace.modules for ev in dev
            if LINK.match(program_name(ev.name))]
    if not secs:
        return None
    return 1e3 * sum(secs) / run.ticks
