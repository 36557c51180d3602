"""Jit'd public wrapper for the matching-engine kernel.

Pads the packet dimension to the kernel block, dispatches to the Pallas
kernel (interpret mode on CPU — the kernel body executes as jnp ops for
validation; compiled Mosaic on TPU) or to the jnp reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.matcher import matcher as _k
from repro.kernels.matcher import ref as _ref


def match(words: jax.Array, rules: jax.Array, modes: jax.Array,
          use_kernel: bool = False, block_n: int = _k.DEFAULT_BLOCK_N):
    """Returns (matched, eom): (N, C) bool.

    ``use_kernel=False`` (default on CPU hot paths) uses the jnp oracle —
    identical results; the Pallas path is exercised by tests/benchmarks and
    is the TPU deployment path.
    """
    if not use_kernel:
        return _ref.match_ref(words, rules, modes)
    n = words.shape[0]
    pad = (-n) % block_n
    if pad:
        words = jnp.pad(words, ((0, pad), (0, 0)))
    matched, eom = _k.match_pallas(words, rules, modes, block_n=block_n,
                                   interpret=kernels.interpret())
    return matched[:n].astype(bool), eom[:n].astype(bool)
