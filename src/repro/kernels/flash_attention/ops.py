"""Jit'd wrapper: GQA-shaped entry point for the flash-attention kernel.

Takes the model's (B, S, H, D) / (B, S, KV, D) layout, folds batch and
head dims into the kernel's flat head axis (broadcasting K/V across the
GQA group), pads sequence to kernel blocks, and dispatches Pallas
(interpret on CPU) or the jnp oracle."""
from __future__ import annotations

import jax.numpy as jnp

from repro import kernels
from repro.kernels.flash_attention import flash_attention as _k
from repro.kernels.flash_attention import ref as _ref


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    use_kernel: bool = False,
                    block_q: int = _k.DEFAULT_BLOCK_Q,
                    block_k: int = _k.DEFAULT_BLOCK_K):
    """q (B, Sq, H, D); k, v (B, Sk, KV, D) -> (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(
        b * h, sk, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(
        b * h, sk, d)
    if not use_kernel:
        of = _ref.flash_attention_ref(qf, kf, vf, causal=causal,
                                      window=window)
    else:
        bq = min(block_q, sq)
        bk = min(block_k, sk)
        pad_q = (-sq) % bq
        pad_k = (-sk) % bk
        if pad_q:
            qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
        if pad_k:
            kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
            vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
        of = _k.flash_attention_pallas(qf, kf, vf, causal=causal,
                                       window=window, block_q=bq,
                                       block_k=bk,
                                       interpret=kernels.interpret())
        of = of[:, :sq]
    return of.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
