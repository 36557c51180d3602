"""The named-bytes FIN read's share of the chip's HBM roofline, in %.

100 × bytes moved ÷ (HBM peak × device seconds) over the ``jit__read_named``
events of the traced window.  Each gather moves
``GATHER_BYTES_PER_NAMED_BYTE`` (``bench/roofline.py``) for each byte its
datatype names.  A run does not say which datatype each call read, so the
count takes the mean named bytes over the configuration's datatypes with
holes: right for ``npb_mg_8r``'s ``comm3``, in which every rank gathers
its axis-1 and axis-2 faces twice an operation (its axis-3 face is dense
and read by slice).  The configuration is read from its file beside this
one; the peak is that of the device the run holds.  None where the window
ran no such read.
"""
import json
from pathlib import Path

from bench import roofline
from bench.trace import program_name

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "npb_mg_8r.json"


def named_bytes_per_call(config: dict) -> float:
    types = roofline.gathered_types(config["datatypes"])
    return sum(types) / len(types)


def read(run):
    if run.trace is None:
        return None
    secs = [ev.seconds for dev in run.trace.modules for ev in dev
            if program_name(ev.name) == "jit__read_named"]
    if not secs:
        return None
    import jax
    peak = roofline.hbm_peak(jax.devices()[0].device_kind)
    moved = len(secs) * roofline.GATHER_BYTES_PER_NAMED_BYTE \
        * named_bytes_per_call(json.loads(CONFIG.read_text()))
    return 100.0 * moved / (peak * sum(secs))
