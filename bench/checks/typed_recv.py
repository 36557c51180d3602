"""The reference of ``typed_recv``: the receive buffer MPI's typemap
semantics give.

The sender's memory is the datatype's span of standard normal float32
values.  Every byte offset the datatype names holds the sender's byte there,
every other byte is zero.  The number compared is ``bytes_wrong``, the count
of bytes that differ; it is exact, so its limit is 0.  The control sends the
memory rounded to bfloat16.
"""
import numpy as np

from bench.reference import bf16, typed_layout


def _datatype(config, name):
    for d in config["datatypes"]:
        if d["name"] == name:
            return d
    raise KeyError(f"datatype {name!r} is not in configuration "
                   f"{config['name']!r}")


def draw(mix, config, rng):
    dt = _datatype(config, mix["datatype"])
    _, span = typed_layout(dt["type"], dt["count"])
    return rng.standard_normal(span // 4).astype(np.float32).view(np.uint8)


def expected(mix, config, inputs):
    """The buffer after a send of ``inputs`` with the datatype and a
    receive of the same datatype into a zeroed buffer."""
    dt = _datatype(config, mix["datatype"])
    offs, span = typed_layout(dt["type"], dt["count"])
    out = np.zeros(span, np.uint8)
    # where blocks overlap the later byte wins; the sender packed the same
    # memory byte into both places, so every write to one offset agrees
    out[offs] = inputs[offs]
    return out


def compare(mix, config, inputs, outputs):
    want = expected(mix, config, inputs)
    got = np.asarray(outputs, np.uint8).reshape(-1)
    if got.size != want.size:
        return {"bytes_wrong": float(want.size)}
    return {"bytes_wrong": float(np.count_nonzero(got != want))}


def control(mix, config, inputs):
    low = bf16(np.asarray(inputs).view(np.float32)).view(np.uint8)
    return expected(mix, config, low)


def small(mix, config):
    """At most 4 ranks; the message is the datatype's, left whole."""
    return mix, dict(config, ranks=min(config["ranks"], 4))
