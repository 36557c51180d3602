"""Seconds from the start of the process to the start of the window.

Imports, building the communicator, loading or compiling every program the
window runs, the warm-up burst and the warm-up operation.
"""


def read(run):
    return run.setup_s
