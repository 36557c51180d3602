"""The plain reference that decides ``correct``, and its lower-precision control.

Nothing here imports the simulator.  The reference computes, from a traffic
mix, a configuration and the inputs the generator drew from the seed, what
every completed operation must have produced:

* ``allreduce``: the elementwise sum of the ranks' vectors in float64.  The
  number compared is ``sum_gap``, the widest gap between a rank's result and
  that sum, over every element of every rank, as a share of the sum of the
  absolute values that went into the element (the scale float32 rounding of
  a sum is bounded by).
* ``typed_recv``: the receive buffer MPI's typemap semantics give: every byte
  offset the datatype names holds the sender's byte there, every other byte is
  zero.  The number compared is ``bytes_wrong``, the count of bytes that
  differ; it is exact, so its limit is 0.

The control puts the same reference, computed one precision step lower, in
the program's place: bfloat16 (round to nearest even) for the float32 data
both mixes carry.  ``compare`` is what the harness runs on the program's
outputs; ``control_outputs`` gives what the control would have produced.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

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


def expected_typed_recv(spec, count: int, mem: np.ndarray) -> np.ndarray:
    """The receive buffer after a send of ``mem`` with the datatype and a
    receive of the same datatype into a zeroed buffer."""
    offs, span = typed_layout(spec, count)
    out = np.zeros(span, np.uint8)
    # where blocks overlap the later byte wins; the sender packed the same
    # memory byte into both places, so every write to one offset agrees
    out[offs] = mem[offs]
    return out


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


def compare(mix: dict, config: dict, inputs, outputs) -> Dict[str, float]:
    """The numbers compared for one completed operation."""
    if mix["op"] == "allreduce":
        if len(outputs) != len(inputs):
            return {"sum_gap": float("inf")}
        return {"sum_gap": sum_gap(outputs, inputs)}
    if mix["op"] == "typed_recv":
        dt = _datatype(config, mix["datatype"])
        want = expected_typed_recv(dt["type"], dt["count"], inputs)
        got = np.asarray(outputs, np.uint8).reshape(-1)
        if got.size != want.size:
            return {"bytes_wrong": float(want.size)}
        return {"bytes_wrong": float(np.count_nonzero(got != want))}
    raise ValueError(f"unknown operation {mix['op']!r}")


def control_outputs(mix: dict, config: dict, inputs):
    """What the reference gives one precision step lower (bfloat16)."""
    if mix["op"] == "allreduce":
        acc = np.zeros_like(np.asarray(inputs[0], np.float32))
        for v in inputs:
            acc = bf16(acc + bf16(np.asarray(v, np.float32)))
        return [acc] * len(inputs)
    if mix["op"] == "typed_recv":
        dt = _datatype(config, mix["datatype"])
        low = bf16(np.asarray(inputs).view(np.float32)).view(np.uint8)
        return expected_typed_recv(dt["type"], dt["count"], low)
    raise ValueError(f"unknown operation {mix['op']!r}")


def _datatype(config: dict, name: str) -> dict:
    for d in config["datatypes"]:
        if d["name"] == name:
            return d
    raise KeyError(f"datatype {name!r} is not in configuration "
                   f"{config['name']!r}")
