"""Pallas TPU kernel for the FPsPIN U32 matching engine (paper §IV block 1).

The hardware matcher inspects one 32-bit word per rule; a ruleset is
3 match rules + 1 EOM rule with an AND/OR combiner.  On TPU we evaluate
*all contexts × all rules* for a block of packets at once, entirely in the
VPU (bitwise ops + compares, no MXU):

  grid:   (N // BLOCK_N,)
  SMEM:   rules  (C * 16,)    int32    -- (C, 4, 4) table, flattened
          modes  (C,)         int32
  VMEM:   words  (W, BLOCK_N) int32    -- the packet word view, transposed
                                          so packets run along the lanes
  out:    matched, eom  (C, BLOCK_N) int32

The rule table lives in SMEM because every rule field is read as a scalar.
Word selection (``words[idx[c, r], :]``) is a broadcasted-iota
compare-and-sum over the sublane axis instead of a dynamic gather: exactly
one word row matches, so the int32 sum returns that word's bit pattern.
Mosaic has no unsigned reductions or compares, so the u32 words travel as
their int32 bit patterns and the unsigned range test ``start <= v <= end``
runs as a signed compare after flipping the sign bit of both sides.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 128
_SIGN = -(1 << 31)              # int32 sign bit: u32 order == (x ^ _SIGN) order


def _matcher_kernel(rules_ref, modes_ref, words_ref, matched_ref, eom_ref,
                    *, n_ctx: int):
    words = words_ref[...]                        # (W, BN) int32
    w, _ = words.shape
    w_iota = jax.lax.broadcasted_iota(jnp.int32, words.shape, 0)

    for c in range(n_ctx):
        oks = []
        for r in range(4):
            base = (c * 4 + r) * 4
            idx = jnp.clip(rules_ref[base], 0, w - 1)
            mask = rules_ref[base + 1]
            start = rules_ref[base + 2] ^ _SIGN
            end = rules_ref[base + 3] ^ _SIGN
            # select word `idx` of every packet (exactly one row matches)
            sel = jnp.sum(jnp.where(w_iota == idx, words, 0), axis=0,
                          keepdims=True)          # (1, BN)
            v = (sel & mask) ^ _SIGN
            oks.append((v >= start) & (v <= end))
        and_mode = oks[0] & oks[1] & oks[2]
        or_mode = oks[0] | oks[1] | oks[2]
        is_and = (modes_ref[c] == 0).astype(jnp.int32)
        matched_ref[c:c + 1, :] = (and_mode.astype(jnp.int32) * is_and
                                   + or_mode.astype(jnp.int32) * (1 - is_and))
        eom_ref[c:c + 1, :] = oks[3].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def match_pallas(words: jax.Array, rules: jax.Array, modes: jax.Array,
                 block_n: int = DEFAULT_BLOCK_N, interpret: bool = True):
    """words (N, W) uint32, rules (C,4,4) uint32, modes (C,) int32.

    Returns (matched, eom): (N, C) int32 each. N must be a multiple of
    block_n (ops.py pads).
    """
    n, w = words.shape
    c = rules.shape[0]
    assert n % block_n == 0, (n, block_n)
    words_t = jax.lax.bitcast_convert_type(words, jnp.int32).T
    rules_flat = jax.lax.bitcast_convert_type(rules, jnp.int32).reshape(-1)
    out_shape = [jax.ShapeDtypeStruct((c, n), jnp.int32)] * 2
    kernel = functools.partial(_matcher_kernel, n_ctx=c)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    matched, eom = pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[smem, smem, pl.BlockSpec((w, block_n), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((c, block_n), lambda i: (0, i))] * 2,
        out_shape=out_shape,
        interpret=interpret,
    )(rules_flat, modes.astype(jnp.int32), words_t)
    return matched.T, eom.T
