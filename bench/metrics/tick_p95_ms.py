"""95th percentile of the wall time of one fabric tick, in milliseconds.

Taken over every ``comm.progress(1)`` call of the window, each timed alone on
the host clock (numpy's linear interpolation between order statistics).
Busy ticks, in which NIC steps run, set it.
"""
import numpy as np


def read(run):
    if not run.ticks:
        return None
    return 1e3 * float(np.percentile(np.asarray(run.tick_s), 95))
