"""Jit'd public wrapper for the checksum kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.checksum import checksum as _k
from repro.kernels.checksum import ref as _ref


def internet_checksum(data: jax.Array, lengths: jax.Array, *, start: int,
                      use_kernel: bool = False,
                      block_n: int = _k.DEFAULT_BLOCK_N) -> jax.Array:
    """Batched RFC1071 checksum over bytes [start, length) per packet."""
    if not use_kernel:
        return _ref.checksum_ref(data, lengths, start)
    n = data.shape[0]
    pad = (-n) % block_n
    if pad:
        data = jnp.pad(data, ((0, pad), (0, 0)))
        lengths = jnp.pad(lengths, (0, pad), constant_values=start)
    out = _k.checksum_pallas(data, lengths, start=start, block_n=block_n,
                             interpret=kernels.interpret())
    return out[:n]
