"""What the references of the operations share.  Nothing here imports the
simulator.

Each operation's reference is ``bench/checks/<op>.py`` (``bench/spec.py``
says what it holds): from a traffic mix, a configuration and the inputs the
generator drew from the seed, it computes what every completed operation
must have produced, the numbers compared, and its control, the same
reference one precision step lower put in the program's place.  The helpers
here are the datatype typemap by the MPI rules, bfloat16 rounding and the
relative gap of a sum.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

_PRIMITIVES = {"float32": 4, "float64": 8, "int32": 4, "byte": 1}


def typemap(spec) -> Tuple[List[int], int]:
    """Byte offsets of one instance of a datatype in serialization order,
    and its extent, by the MPI rules for contiguous, vector and hvector
    types (lower bound 0, positive strides)."""
    if isinstance(spec, str):
        n = _PRIMITIVES[spec]
        return list(range(n)), n
    (kind, a), = spec.items()
    base, base_extent = typemap(a["base"])
    if kind == "contiguous":
        starts = [i * base_extent for i in range(a["count"])]
        extent = a["count"] * base_extent
    elif kind == "vector":
        starts = [(i * a["stride"] + j) * base_extent
                  for i in range(a["count"]) for j in range(a["blocklen"])]
        extent = ((a["count"] - 1) * a["stride"] + a["blocklen"]) \
            * base_extent
    elif kind == "hvector":
        starts = [i * a["stride_bytes"] + j * base_extent
                  for i in range(a["count"]) for j in range(a["blocklen"])]
        extent = (a["count"] - 1) * a["stride_bytes"] \
            + a["blocklen"] * base_extent
    else:
        raise ValueError(f"unknown datatype constructor {kind!r}")
    offsets = [s + b for s in starts for b in base]
    return offsets, extent


def typed_layout(spec, count: int) -> Tuple[np.ndarray, int]:
    """Offsets of ``count`` consecutive instances and their memory span."""
    one, extent = typemap(spec)
    one = np.asarray(one, np.int64)
    offs = (np.arange(count, dtype=np.int64)[:, None] * extent
            + one[None, :]).reshape(-1)
    return offs, count * extent


def bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32).astype(np.uint64)
    r = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return r.astype(np.uint32).view(np.float32)


def sum_gap(outputs: Sequence[np.ndarray],
            inputs: Sequence[np.ndarray]) -> float:
    x = np.stack([np.asarray(v, np.float64).reshape(-1) for v in inputs])
    want = x.sum(axis=0)
    scale = np.abs(x).sum(axis=0)
    worst = 0.0
    for out in outputs:
        gap = np.abs(np.asarray(out, np.float64).reshape(-1) - want)
        with np.errstate(divide="ignore", invalid="ignore"):
            rel = np.where(scale > 0, gap / scale,
                           np.where(gap > 0, np.inf, 0.0))
        worst = max(worst, float(rel.max()))
    return worst
