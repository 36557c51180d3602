"""Jit'd public wrappers: pack / unpack / gather for DDT processing.

``pack``   : serialize a non-contiguous source buffer into a message
             (out[i] = buf[pack_idx[i]]).
``unpack`` : scatter a packed message into a destination buffer
             (dst[j]  = msg[unpack_idx[j]] where unpack_idx[j] >= 0,
              else keep dst[j]).

Both are expressed through one gather primitive; the index maps come from
:mod:`repro.core.ddt` (the dataloop "commit" step).  Padding to kernel
blocks happens here so callers never see alignment constraints.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.ddt import ddt as _k
from repro.kernels.ddt import ref as _ref


def gather(src: jax.Array, idx: jax.Array, *, fill=0,
           use_kernel: bool = False,
           block_i: int = _k.DEFAULT_BLOCK_I,
           block_s: int = _k.DEFAULT_BLOCK_S) -> jax.Array:
    """out[i] = src[idx[i]] (idx -1 -> fill). 1-D src/idx, any dtype."""
    if not use_kernel:
        return _ref.ddt_gather_ref(src, idx, fill)
    s, i = src.shape[0], idx.shape[0]
    pad_s = (-s) % block_s
    pad_i = (-i) % block_i
    if pad_s:
        src = jnp.pad(src, (0, pad_s))
    if pad_i:
        idx = jnp.pad(idx, (0, pad_i), constant_values=-1)
    out = _k.ddt_gather_pallas(src, idx, fill=fill, block_i=block_i,
                               block_s=block_s, interpret=kernels.interpret())
    return out[:i]


def pack(buf: jax.Array, pack_idx: jax.Array, use_kernel: bool = False
         ) -> jax.Array:
    """Serialize: message[i] = buf[pack_idx[i]]."""
    return gather(buf, pack_idx, fill=0, use_kernel=use_kernel)


def unpack(msg: jax.Array, unpack_idx: jax.Array, dst: jax.Array,
           use_kernel: bool = False) -> jax.Array:
    """De-serialize into dst: positions with unpack_idx >= 0 receive
    msg[unpack_idx]; others keep their existing value (datatype holes)."""
    gathered = gather(msg, unpack_idx, fill=0, use_kernel=use_kernel)
    return jnp.where(unpack_idx >= 0, gathered, dst)
