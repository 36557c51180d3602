"""Host milliseconds spent in blocking device-to-host reads per fabric tick.

Sums the durations of the program's ``d2h.*`` spans in the traced window
(``bench/spans.py``), each the host's wait for the device and the copy,
divided by the fabric ticks of the window.  None where the window holds no
program span.
"""
from bench.spans import D2H, program_spans


def read(run):
    if run.trace is None or not run.ticks:
        return None
    spans = program_spans(run.trace)
    if not spans:
        return None
    return 1e3 * sum(ev.seconds for ev in spans if ev.name in D2H) \
        / run.ticks
