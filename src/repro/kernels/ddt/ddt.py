"""Pallas TPU kernel for MPI derived-datatype (un)pack — ``ddt_gather``.

FPsPIN's hottest loop is the MPICH *dataloop* engine running on 40 MHz HPU
cores, walking nested vector/hvector descriptors byte by byte (paper §V-C).
The TPU-native adaptation (DESIGN.md §2) compiles the datatype **once** into
an element index map (runtime code specialization, the technique the paper
cites as [44]) and turns both pack and unpack into a single primitive:

    out[i] = idx[i] >= 0 ? src[idx[i]] : fill

executed as a tiled, accumulate-over-source-blocks kernel:

  grid:  (I // BI, S // BS)          I = index count, S = source elements
  VMEM:  idx  (1, BI) int32          out tile's source indices
         src  (1, BS) dtype          one source block
  out:   (1, BI) dtype, revisited across the S dimension (accumulation)

Each source block contributes ``where(idx - base == iota, src, 0)`` summed
over the block — an exact masked-select gather that never needs a dynamic
vector gather (works for all dtypes, MXU-free, fully vectorized on the
VPU).  Exactly one source block contributes per element, so ``+=`` across
the grid's S dimension reconstructs the gather exactly (zero is the
additive identity for the masked lanes in every dtype).

The kernel body only ever sees 32-bit elements: Mosaic reduces int32 and
f32 but not unsigned or 8/16-bit vectors, so the wrapper widens narrower
sources (the NIC's uint8 payloads among them) to int32/f32 and reinterprets
uint32 as int32, then narrows the result back — both exact.

VMEM budget per step: BI*4 + BS*4 + BI*BS*4 bytes for the broadcast
compare; defaults (BI=512, BS=512) ≈ 1.05 MiB — comfortably inside
16 MiB VMEM with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK_I = 512
DEFAULT_BLOCK_S = 512


def _gather_kernel(idx_ref, src_ref, out_ref, *, block_s: int, fill):
    s_blk = pl.program_id(1)
    idx = idx_ref[...]                               # (1, BI) int32
    src = src_ref[...]                               # (1, BS) dtype
    dtype = src.dtype
    base = (s_blk * block_s).astype(jnp.int32)
    rel = idx - base                                 # (1, BI)
    bi = idx.shape[1]
    # (BI, BS) compare grid: rel[i] == s for the in-block source position
    s_iota = jax.lax.broadcasted_iota(jnp.int32, (bi, block_s), 1)
    hit = rel.reshape(bi, 1) == s_iota               # (BI, BS) bool
    contrib = jnp.where(hit, jnp.broadcast_to(src.reshape(1, block_s),
                                              (bi, block_s)),
                        jnp.zeros((), dtype))
    partial = contrib.sum(axis=1).reshape(1, bi)

    @pl.when(s_blk == 0)
    def _init():
        # negative index -> fill value (holes in the datatype)
        out_ref[...] = jnp.where(idx < 0, jnp.asarray(fill, dtype),
                                 jnp.zeros((), dtype))

    out_ref[...] += partial


@functools.partial(jax.jit,
                   static_argnames=("block_i", "block_s", "interpret",
                                    "fill"))
def ddt_gather_pallas(src: jax.Array, idx: jax.Array, *, fill=0,
                      block_i: int = DEFAULT_BLOCK_I,
                      block_s: int = DEFAULT_BLOCK_S,
                      interpret: bool = True) -> jax.Array:
    """src (S,) dtype; idx (I,) int32 with -1 = hole.  Returns (I,) dtype.

    S % block_s == 0 and I % block_i == 0 (ops.py pads).
    """
    (s,) = src.shape
    (i,) = idx.shape
    assert s % block_s == 0 and i % block_i == 0, (s, i, block_s, block_i)
    dtype = src.dtype
    if dtype.itemsize < 4:               # widen: exact for every narrow type
        src = src.astype(jnp.float32 if jnp.issubdtype(dtype, jnp.floating)
                         else jnp.int32)
    elif dtype == jnp.uint32:            # reinterpret the bits as int32
        src = jax.lax.bitcast_convert_type(src, jnp.int32)
        fill = int(np.asarray(fill, np.uint32).view(np.int32))
    grid = (i // block_i, s // block_s)
    kernel = functools.partial(_gather_kernel, block_s=block_s, fill=fill)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_i), lambda ib, sb: (0, ib)),
            pl.BlockSpec((1, block_s), lambda ib, sb: (0, sb)),
        ],
        out_specs=pl.BlockSpec((1, block_i), lambda ib, sb: (0, ib)),
        out_shape=jax.ShapeDtypeStruct((1, i), src.dtype),
        interpret=interpret,
    )(idx.reshape(1, i), src.reshape(1, s))
    if dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(out.reshape(i), dtype)
    return out.reshape(i).astype(dtype)
