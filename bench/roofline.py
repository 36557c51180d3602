"""Peaks of the chips the benchmark runs on, and the bytes a kernel of the
program moves, for the per-layer metrics that read a share of a roofline.

``HBM_BYTES_PER_S`` is keyed by ``jax.Device.device_kind``; a device that
is not in it is an error, never a default.  Source: Google Cloud
documentation, "TPU v5e" (16 GB of HBM at 819 GB/s a chip).
"""
from __future__ import annotations

from typing import Iterable

import numpy as np

from bench.reference import typed_layout

HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}

# the FIN gather ``SpinNIC.read_named`` (``jit__read_named``) moves, for
# each byte a datatype names: its int32 offset read, the byte read from the
# DMA window and the byte written to the output
GATHER_BYTES_PER_NAMED_BYTE = 4 + 1 + 1


def hbm_peak(device_kind: str) -> float:
    if device_kind not in HBM_BYTES_PER_S:
        raise KeyError(f"no HBM peak for device kind {device_kind!r}; add "
                       f"it to bench/roofline.py with its source")
    return HBM_BYTES_PER_S[device_kind]


def named_bytes(spec, count: int = 1) -> int:
    """Distinct bytes ``count`` instances of the datatype ``spec`` name."""
    offs, _ = typed_layout(spec, count)
    return int(np.unique(offs).size)


def gathered_types(datatypes: Iterable[dict]) -> list:
    """Named bytes of each configuration datatype whose extent has holes:
    the types a FIN reads by gather, the others by one slice."""
    out = []
    for d in datatypes:
        named = named_bytes(d["type"], d["count"])
        if named < typed_layout(d["type"], d["count"])[1]:
            out.append(named)
    return out
