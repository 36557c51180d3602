"""Pallas kernels for the NIC datapath's compute hot-spots, each with a
jnp reference (``ref.py``) and a public wrapper (``ops.py``)."""
from __future__ import annotations

import jax


def interpret() -> bool:
    """Whether Pallas kernels run in interpret mode on the default backend.

    True on the CPU (the kernel body runs as jnp ops, for validation),
    False on the TPU (Mosaic compiles it).  Any other platform has no
    Pallas path here and raises, so a kernel never runs interpreted on an
    accelerator without anyone noticing.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for platform {platform!r}")
