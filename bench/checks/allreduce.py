"""The reference of ``allreduce``: the elementwise sum of the ranks'
vectors in float64.

Each rank's vector holds ``bytes_per_rank`` of standard normal ``dtype``
values.  The number compared is ``sum_gap``, the widest gap between a rank's
result and that sum, over every element of every rank, as a share of the
sum of the absolute values that went into the element (the scale float32
rounding of a sum is bounded by).  The control sums in bfloat16.
"""
import numpy as np

from bench.reference import bf16, sum_gap


def draw(mix, config, rng):
    n = mix["bytes_per_rank"] // np.dtype(mix["dtype"]).itemsize
    return [rng.standard_normal(n).astype(mix["dtype"])
            for _ in range(config["ranks"])]


def expected(mix, config, inputs):
    total = np.sum(np.stack(inputs).astype(np.float64), axis=0)
    return [total.astype(mix["dtype"])] * len(inputs)


def compare(mix, config, inputs, outputs):
    if len(outputs) != len(inputs):
        return {"sum_gap": float("inf")}
    return {"sum_gap": sum_gap(outputs, inputs)}


def control(mix, config, inputs):
    acc = np.zeros_like(np.asarray(inputs[0], np.float32))
    for v in inputs:
        acc = bf16(acc + bf16(np.asarray(v, np.float32)))
    return [acc] * len(inputs)


def small(mix, config):
    """At most 4 ranks and 128 KiB a rank: small enough for the CPU, and
    above the size at which an allreduce goes segmented Rabenseifner."""
    return (dict(mix, bytes_per_rank=min(mix["bytes_per_rank"], 128 << 10)),
            dict(config, ranks=min(config["ranks"], 4)))
