"""Modelled fabric ticks per second of wall-clock.

Every ``comm.progress(1)`` call the window made (the in-flight operation's
ticks included), divided by the window's seconds on the host clock, from its
start to the return of ``jax.block_until_ready`` on every live device array
after the last tick.  A user's wait for a run is its modelled ticks divided
by this number.
"""


def read(run):
    if not run.ticks:
        return None
    return run.ticks / run.window_s
