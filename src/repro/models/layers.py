"""Shared neural-net layers: norms, rotary embeddings, MLP variants.

All layers are pure functions over parameter pytrees (dicts).  Init
functions only build ``jax.ShapeDtypeStruct``-compatible shapes through
``jax.eval_shape`` when used for sharding specs, so nothing here may
allocate eagerly at import time.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ------------------------------------------------------------------- norms
def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    orig = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(orig)


# -------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float
               ) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections=None) -> jax.Array:
    """Qwen2-VL M-RoPE: positions (3, B, S) = (temporal, h, w); the head
    dim's frequency slots are split into three sections, each rotated by
    its own position component.  sections are in *frequency pairs* and
    must sum to head_dim/2.  Default split = (1/4, 3/8, 3/8) of the pairs,
    i.e. (16, 24, 24) for head_dim 128 — the Qwen2-VL configuration."""
    d = x.shape[-1]
    if sections is None:
        t = d // 8
        h = (d // 2 - t) // 2
        sections = (t, h, d // 2 - t - h)
    assert sum(sections) == d // 2, (sections, d)
    freqs = jnp.asarray(rope_freqs(d, theta), jnp.float32)        # (D/2,)
    # component id per frequency slot
    comp = np.concatenate([np.full(s, i) for i, s in enumerate(sections)])
    pos = positions.astype(jnp.float32)                           # (3,B,S)
    pos_per_slot = jnp.take(pos, jnp.asarray(comp), axis=0)       # (D/2,B,S)
    angles = jnp.moveaxis(pos_per_slot, 0, -1) * freqs            # (B,S,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def sinusoid_positions(seq: int, d: int) -> np.ndarray:
    """Whisper-style sinusoidal absolute position embedding table."""
    pos = np.arange(seq)[:, None]
    dim = np.arange(0, d, 2)[None, :]
    angle = pos / (10000 ** (dim / d))
    out = np.zeros((seq, d), np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return out


# --------------------------------------------------------------------- MLP
def mlp_init(key, cfg: ModelConfig, d_ff: int) -> Params:
    d, dt = cfg.d_model, _dtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = float(1.0 / np.sqrt(d))
    s_out = float(1.0 / np.sqrt(d_ff))
    p = {"up": jax.random.normal(k1, (d, d_ff), dt) * s_in,
         "down": jax.random.normal(k2, (d_ff, d), dt) * s_out}
    if cfg.mlp_kind in ("swiglu", "geglu"):
        p["gate"] = jax.random.normal(k3, (d, d_ff), dt) * s_in
    return p


def mlp_apply(p: Params, x: jax.Array, kind: str) -> jax.Array:
    up = x @ p["up"]
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["gate"]) * up
    elif kind == "geglu":
        h = jax.nn.gelu(x @ p["gate"], approximate=True) * up
    elif kind == "squared_relu":                     # nemotron-4
        h = jnp.square(jax.nn.relu(up))
    elif kind == "gelu":
        h = jax.nn.gelu(up, approximate=True)
    else:
        raise ValueError(kind)
    return h @ p["down"]


# --------------------------------------------------------------- embedding
def embed_init(key, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    v = cfg.padded_vocab
    p = {"tok": jax.random.normal(k1, (v, cfg.d_model), dt) * 0.02}
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(
            k2, (cfg.d_model, v), dt) * float(1.0 / np.sqrt(cfg.d_model))
    return p


def embed_tokens(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0)


def lm_logits(p: Params, x: jax.Array, tie: bool,
              out_dtype=jnp.float32, true_vocab: int = 0) -> jax.Array:
    """Logits over the (possibly padded) vocab; padded lanes get -1e9 so
    the CE logsumexp ignores them."""
    w = p["tok"].T if tie else p["lm_head"]
    logits = (x @ w).astype(out_dtype)
    v = w.shape[-1]
    if true_vocab and true_vocab < v:
        lane = jnp.arange(v)
        logits = jnp.where(lane < true_vocab, logits,
                           jnp.asarray(-1e9, out_dtype))
    return logits
