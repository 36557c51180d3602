"""Device program executions per fabric tick.

Counts the events of every device's ``XLA Modules`` line that start inside
the traced window (each is one execution of a compiled program: the link
pop and push, each busy node's NIC step, the small programs of the host's
reads and writes of NIC state), divided by the fabric ticks of the window.
"""


def read(run):
    if run.trace is None or not run.ticks:
        return None
    calls = sum(len(dev) for dev in run.trace.modules)
    if not calls:
        return None
    return calls / run.ticks
