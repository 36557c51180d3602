"""Share of the traced window in which the device ran no operation.

100 x (1 - busy / window), where busy is the union of the intervals of the
events on each device's ``XLA Ops`` line that fall in the window, averaged
over the devices the run used, and window is the harness's ``window`` span.
"""


def read(run):
    if run.trace is None or not run.trace.busy:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
