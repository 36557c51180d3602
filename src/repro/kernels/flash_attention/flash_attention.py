"""Pallas TPU flash-attention kernel (forward): the §Perf answer to the
HLO attention floor.

About 80 % of an LLM train step's memory term is S²-shaped
score/probability traffic that HLO *must* materialize between the QKᵀ
and PV dots.  A fused kernel keeps
those blocks in VMEM: HBM sees only Q, K, V, O — the flash-attention
trade.  This kernel implements the online-softmax streaming form with
explicit BlockSpec tiling:

  grid:  (B·KV·G heads, Sq/BQ, Sk/BK)   — causal/window blocks that are
                                           fully masked are skipped via
                                           pl.when on the block indices
  VMEM:  q (BQ, D), k/v (BK, D), f32 scratch: acc (BQ, D), m/l (BQ,)
  HBM:   q, k, v in; o out — no S² tensor ever leaves VMEM

Numerics match models/attention.blockwise_attention (same online-softmax
recurrence, f32 stats): validated in interpret mode against it in
tests/test_kernels.py.  Backward runs through recompute
(jax.checkpoint around the op); the fwd kernel is where the S² traffic
lived.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, window: int, block_q: int, block_k: int,
                  nk: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    # block-level static-shape mask test (traced on block indices)
    run = jnp.bool_(True)
    if causal:
        run = run & (ki * block_k <= qi * block_q + block_q - 1)
    if window > 0:
        run = run & (ki * block_k + block_k - 1 > qi * block_q - window)

    @pl.when(run)
    def _body():
        q = q_ref[...].astype(jnp.float32) * scale   # (BQ, D)
        k = k_ref[...].astype(jnp.float32)           # (BK, D)
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (BQ, BK)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int = 0,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = True):
    """q (H, Sq, D); k, v (H, Sk, D) — heads flattened (B·KV·G for GQA,
    with k/v pre-broadcast per group).  Returns (H, Sq, D).
    Sq % block_q == 0 and Sk % block_k == 0 (ops.py pads)."""
    h, sq, d = q.shape
    _, sk, _ = k.shape
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0
    nq, nk = sq // bq, sk // bk
    scale = float(1.0 / np.sqrt(d))
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, block_q=bq,
        block_k=bk, nk=nk, scale=scale)
    scratch = [pltpu.VMEM((bq,), jnp.float32), pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq, d), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda hh, qi, ki: (hh, qi, 0)),
            pl.BlockSpec((None, bk, d), lambda hh, qi, ki: (hh, ki, 0)),
            pl.BlockSpec((None, bk, d), lambda hh, qi, ki: (hh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, d),
                               lambda hh, qi, ki: (hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((h, sq, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
