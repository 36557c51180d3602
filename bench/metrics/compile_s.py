"""Seconds of XLA compilation during set-up.

Summed from ``jax.monitoring``'s ``/jax/core/compile/backend_compile_duration``
events between the start of the process and the start of the window; a
program found in the persistent compilation cache counts the time taken to
load it.
"""


def read(run):
    return run.setup_compile_s
