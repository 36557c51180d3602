"""GQA attention: blockwise-streaming (flash-style) prefill/train path and
O(cache) decode path, with full / sliding-window / bidirectional / cross
variants.

The train/prefill path never materializes an (S × S) score matrix: it
scans over KV blocks with a running-max online softmax (f32 accumulators),
so activation memory is O(S · block) — required for the 32 k-token prefill
shapes and the long-context cells of the assignment.  Sliding-window
layers bound compute too: each query block attends to a
``dynamic_slice``-d KV span of width ``window + block``, making local
attention O(S · window) — this is what lets gemma3/recurrentgemma run the
524 k decode cell.

GQA is expressed by folding query heads into groups over the KV heads;
with model-axis sharding on the KV head dimension the same code serves
MHA (kv == heads) down to MQA (kv == 1, replicated KV).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L

NEG_INF = -1e30

def attn_init(key, cfg: ModelConfig, cross: bool = False):
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = float(1.0 / np.sqrt(d))
    p = {
        "wq": jax.random.normal(kq, (d, cfg.q_dim), dt) * s,
        "wk": jax.random.normal(kk, (d, cfg.kv_dim), dt) * s,
        "wv": jax.random.normal(kv, (d, cfg.kv_dim), dt) * s,
        "wo": jax.random.normal(ko, (cfg.q_dim, d), dt) * s,
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dt)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dt)
    return p


def _project_qkv(p, cfg: ModelConfig, x, kv_x, positions, kv_positions):
    """Returns q (B,Sq,H,D), k/v (B,Sk,KV,D) with RoPE applied."""
    b, sq, _ = x.shape
    sk = kv_x.shape[1]
    q = x @ p["wq"]
    k = kv_x @ p["wk"]
    v = kv_x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, sq, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, sk, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, sk, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = L.rmsnorm({"scale": p["q_norm"]}, q, cfg.norm_eps)
        k = L.rmsnorm({"scale": p["k_norm"]}, k, cfg.norm_eps)
    if positions is not None and cfg.pos_kind == "rope":
        if cfg.mrope:
            q = L.apply_mrope(q, positions, cfg.rope_theta)
            k = L.apply_mrope(k, kv_positions, cfg.rope_theta)
        else:
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def _sdpa_block(q, k, v, mask, scale):
    """q (B,KV,G,bq,D); k/v (B,KV,bk,D); mask (bq,bk) or (B,1,1,bq,bk)."""
    s = jnp.einsum("bkgqd,bktd->bkgqt", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    return s


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_offset=0, kv_len: Optional[jax.Array] = None,
                        block_q: int = 512, block_k: int = 512,
                        score_dtype=jnp.float32):
    """Online-softmax attention.

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D).  H % KV == 0.
    causal: causal mask with query i at absolute position q_offset + i.
    window > 0: sliding window (attend to positions in (pos-window, pos]).
    kv_len: optional (B,) valid KV length (encoder padding / cache fill).
    Returns (B, Sq, H, D).
    """
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    scale = float(1.0 / np.sqrt(d))
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k
    nq, nk = sq_p // bq, sk_p // bk
    q = q * scale                    # fold softmax scale into q (one pass
    #                                  over O(S·d) instead of O(S²) scores)
    qb = q.reshape(b, nq, bq, kvh, g, d).transpose(1, 0, 3, 4, 2, 5)
    # (nq, B, KV, G, bq, D)
    kb = k.reshape(b, nk, bk, kvh, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, bk, kvh, d).transpose(1, 0, 3, 2, 4)
    k_valid = jnp.arange(sk_p)                       # (Sk,)

    def one_q_block(qi, qblk):
        q_pos = q_offset + qi * bq + jnp.arange(bq)   # (bq,) absolute

        if window > 0 and sk_p > (window // bk + 2) * bk:
            # local attention: slice only the needed KV span
            span = ((window + bq) // bk + 2) * bk
            start = jnp.clip(qi * bq + bq - span + (sk_p - sq_p), 0,
                             sk_p - span)
            ks = jax.lax.dynamic_slice_in_dim(
                k.reshape(b, sk_p, kvh, d), start, span, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(
                v.reshape(b, sk_p, kvh, d), start, span, axis=1)
            kpos = start + jnp.arange(span)
            s = jnp.einsum("bqkgd,btkd->bkgqt",
                           qblk.transpose(0, 3, 1, 2, 4).reshape(
                               b, bq, kvh, g, d),
                           ks, preferred_element_type=jnp.float32)
            mask = (kpos[None, :] <= q_pos[:, None]) & \
                   (kpos[None, :] > q_pos[:, None] - window)
            if kv_len is not None:
                mask = mask[None] & (kpos[None, None, :] < kv_len[:, None,
                                                                  None])
                mask = mask[:, None, None]
            s = jnp.where(mask, s, NEG_INF)
            o = jax.nn.softmax(s, axis=-1).astype(score_dtype)
            out = jnp.einsum("bkgqt,btkd->bkgqd", o,
                             vs.astype(score_dtype),
                             preferred_element_type=jnp.float32)
            return out.astype(q.dtype)

        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, kblk, vblk = inputs
            kpos = ki * bk + jnp.arange(bk)
            # the QK dot *emits* score_dtype (bf16 halves the S²-shaped
            # HBM traffic — accumulation inside the dot stays f32 on the
            # MXU); max/exp/sum statistics run in f32 via fused converts.
            s = _make_scores(qblk, kblk, q_pos, kpos)   # score_dtype
            new_m = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
            # convert+sub+exp+convert fuse: reads s (bf16), writes p (bf16)
            p = jnp.exp(s.astype(jnp.float32)
                        - new_m[..., None]).astype(score_dtype)
            corr = jnp.exp(m - new_m)
            l = l * corr + p.astype(jnp.float32).sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqt,bktd->bkgqd", p, vblk.astype(score_dtype),
                preferred_element_type=jnp.float32)
            return (new_m, l, acc), None

        def _make_scores(qblk_scaled, kblk, q_pos, kpos):
            # scale is pre-folded into q (one pass over the small tensor
            # instead of one pass over the S²-shaped scores)
            s = jnp.einsum("bkgqd,bktd->bkgqt", qblk_scaled, kblk,
                           preferred_element_type=score_dtype)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= kpos[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= kpos[None, :] > q_pos[:, None] - window
            neg = jnp.asarray(NEG_INF, score_dtype)
            s = jnp.where(mask[None, None, None], s, neg)
            if kv_len is not None:
                live = kpos[None, :] < kv_len[:, None]          # (B, bk)
                s = jnp.where(live[:, None, None, None, :], s, neg)
            return s

        m0 = jnp.full((b, kvh, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, bq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)

    outs = jax.lax.map(lambda args: one_q_block(*args),
                       (jnp.arange(nq), qb))
    # (nq, B, KV, G, bq, D) -> (B, Sq, H, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq_p, h, d)
    return out[:, :sq]


def attend_train(p, cfg: ModelConfig, x, positions, *, kind: str,
                 enc_out=None, enc_positions=None, enc_len=None,
                 causal=True, return_kv: bool = False):
    """Full-sequence attention for train/prefill.  kind: attn|local|cross.
    Returns (B, S, d_model) or ((B,S,d), (k, v)) when return_kv."""
    sdt = jnp.dtype(cfg.attn_scores_dtype)
    if kind == "cross":
        q, k, v = _project_qkv(p, cfg, x, enc_out, None, None)
        out = blockwise_attention(q, k, v, causal=False, kv_len=enc_len,
                                  block_q=cfg.attn_block_q,
                                  block_k=cfg.attn_block_k,
                                  score_dtype=sdt)
    else:
        q, k, v = _project_qkv(p, cfg, x, x, positions, positions)
        out = blockwise_attention(
            q, k, v, causal=causal,
            window=cfg.window if kind == "local" else 0,
            block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
            score_dtype=sdt)
    b, s = x.shape[:2]
    y = out.reshape(b, s, cfg.q_dim) @ p["wo"]
    if return_kv:
        return y, (k, v)
    return y


def fill_kv_cache(cache_k, cache_v, k, v, kind: str, window: int):
    """Write a prefill's K/V (B, S, KV, D) into a decode cache.

    Full attention: positions [0, S) go to slots [0, S).  Local: only the
    last ``window`` positions survive, at their ring-buffer slots
    (slot = pos % window), matching attend_decode's addressing."""
    s = k.shape[1]
    c = cache_k.shape[1]
    if kind == "local" and s > c:
        pos = jnp.arange(s - c, s)
        slots = pos % c
        cache_k = cache_k.at[:, slots].set(k[:, s - c:])
        cache_v = cache_v.at[:, slots].set(v[:, s - c:])
    else:
        n = min(s, c)
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k[:, :n], 0, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v[:, :n], 0, axis=1)
    return cache_k, cache_v


def attend_decode(p, cfg: ModelConfig, x, cache_k, cache_v, pos, *,
                  kind: str, positions=None):
    """Single-token decode.  x: (B, 1, d); cache_k/v: (B, C, KV, D) where
    C = max_seq (full) or window (local, ring buffer).  pos: () or (B,)
    absolute position of the new token.  Returns (y, cache_k, cache_v)."""
    b = x.shape[0]
    c = cache_k.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    if positions is None:
        positions = pos[:, None]                      # (B, 1)
    q, k_new, v_new = _project_qkv(p, cfg, x, x, positions, positions)
    slot = pos % c if kind == "local" else pos        # ring buffer for local
    cache_k = jax.vmap(
        lambda ck, kn, s: jax.lax.dynamic_update_slice_in_dim(ck, kn, s, 0)
    )(cache_k, k_new, slot)
    cache_v = jax.vmap(
        lambda cv, vn, s: jax.lax.dynamic_update_slice_in_dim(cv, vn, s, 0)
    )(cache_v, v_new, slot)

    g = cfg.n_heads // cfg.n_kv_heads
    qh = q.reshape(b, cfg.n_kv_heads, g, cfg.head_dim)
    s = jnp.einsum("bkgd,btkd->bkgt", qh, cache_k,
                   preferred_element_type=jnp.float32)
    s = s / np.sqrt(cfg.head_dim)
    # validity: absolute position of each cache slot
    slots = jnp.arange(c)[None, :]                    # (1, C)
    if kind == "local":
        # slot t holds absolute position: the most recent p <= pos with
        # p % c == t
        abs_pos = pos[:, None] - ((pos[:, None] - slots) % c)
        live = (abs_pos >= 0) & (abs_pos > pos[:, None] - cfg.window) & \
               (abs_pos <= pos[:, None])
    else:
        live = slots <= pos[:, None]
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    o = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", o.astype(cache_v.dtype), cache_v)
    y = out.reshape(b, 1, cfg.q_dim) @ p["wo"]
    return y, cache_k, cache_v


def attend_decode_cross(p, cfg: ModelConfig, x, enc_k, enc_v, enc_len):
    """Cross-attention during decode: enc K/V precomputed at prefill."""
    b = x.shape[0]
    q = (x @ p["wq"]).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    g = cfg.n_heads // cfg.n_kv_heads
    qh = q.reshape(b, cfg.n_kv_heads, g, cfg.head_dim)
    s = jnp.einsum("bkgd,btkd->bkgt", qh, enc_k,
                   preferred_element_type=jnp.float32)
    s = s / np.sqrt(cfg.head_dim)
    if enc_len is not None:
        live = jnp.arange(enc_k.shape[1])[None, :] < enc_len[:, None]
        s = jnp.where(live[:, None, None, :], s, NEG_INF)
    o = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", o.astype(enc_v.dtype), enc_v)
    return out.reshape(b, 1, cfg.q_dim) @ p["wo"]
