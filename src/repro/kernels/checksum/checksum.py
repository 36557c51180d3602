"""Pallas TPU kernel: batched RFC1071 internet checksum (ICMP responder).

The paper's ICMP ping-pong handler spends its time in a portable-C
ones-complement checksum loop — the dominant cost of Fig 7's linear RTT
growth.  The batched TPU form: one grid step checksums BLOCK_N packets at
once; every 32-bit big-endian word holds two u16 checksum words, lanes
beyond each packet's length are masked, and the 32-bit partial sum is
end-around-carry folded.

  grid:  (N // BLOCK_N,)
  VMEM:  words (BLOCK_N, MTU/4) int32  -- big-endian u32 words, packed
                                          from the bytes by the wrapper
         meta  (BLOCK_N, 1)     int32  -- payload byte length (from `start`)
  out:   (BLOCK_N, 1) int32            -- folded ~sum & 0xffff

The wrapper packs bytes into 32-bit words so the kernel never splits the
lane dimension (Mosaic refuses that reshape) and sums in int32 (Mosaic has
no unsigned reductions; 768 words of 0xffff fit easily).

``start`` (the L4 offset, 34 for ICMP) is static.  Bytes past ``length``
must be zero in the buffer (PacketBatch guarantees this); the word mask
only needs whole-word granularity because a trailing odd byte pairs with a
guaranteed-zero pad byte — the same trick the C handler uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 128


def _checksum_kernel(words_ref, len_ref, out_ref, *, start: int):
    words = words_ref[...]                      # (BN, MTU/4) int32
    nbytes = len_ref[...]                       # (BN, 1) int32
    hi = jax.lax.shift_right_logical(words, 16)     # u16 word 2j
    lo = words & 0xFFFF                             # u16 word 2j + 1
    j = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1)
    first = start // 2                          # start is even (34)
    last = (nbytes + 1) // 2                    # exclusive u16 word index
    live_hi = (2 * j >= first) & (2 * j < last)
    live_lo = (2 * j + 1 >= first) & (2 * j + 1 < last)
    s = jnp.sum(jnp.where(live_hi, hi, 0) + jnp.where(live_lo, lo, 0),
                axis=1, keepdims=True)
    # end-around carry: sum of <=768 0xffff words fits 31 bits; two folds
    s = (s & 0xFFFF) + (s >> 16)
    s = (s & 0xFFFF) + (s >> 16)
    out_ref[...] = (~s) & 0xFFFF


def _pack_be32(data: jax.Array) -> jax.Array:
    """(N, 4k) uint8 -> (N, k) int32 holding big-endian u32 bit patterns."""
    b = data.astype(jnp.int32).reshape(data.shape[0], -1, 4)
    return (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]


@functools.partial(jax.jit, static_argnames=("start", "block_n", "interpret"))
def checksum_pallas(data: jax.Array, lengths: jax.Array, *, start: int,
                    block_n: int = DEFAULT_BLOCK_N,
                    interpret: bool = True) -> jax.Array:
    """data (N, MTU) uint8, lengths (N,) int32 -> (N,) uint32 checksums."""
    n, mtu = data.shape
    assert n % block_n == 0 and mtu % 4 == 0
    kernel = functools.partial(_checksum_kernel, start=start)
    out = pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, mtu // 4), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=interpret,
    )(_pack_be32(data), lengths.reshape(n, 1).astype(jnp.int32))
    return out.reshape(n).astype(jnp.uint32)
