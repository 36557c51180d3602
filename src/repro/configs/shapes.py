"""Assigned input-shape suites and ``input_specs`` stand-ins.

Four shapes per architecture (40 cells):

  train_4k    : seq 4096,   global_batch 256  -> train_step
  prefill_32k : seq 32768,  global_batch 32   -> prefill (serve)
  decode_32k  : seq 32768,  global_batch 128  -> serve_step (1 new token,
                                                 KV cache of 32768)
  long_500k   : seq 524288, global_batch 1    -> serve_step; requires
                sub-quadratic attention — runs only for SSM / hybrid /
                mostly-local archs, skipped (and recorded) otherwise.

``input_specs`` returns weak-type-correct ``jax.ShapeDtypeStruct``
stand-ins (or concrete arrays for smoke tests) for every model input —
no device allocation for abstract shapes.  Modality frontends are stubs:
whisper gets precomputed frame embeddings, qwen2-vl gets patch embeddings
and M-RoPE position ids.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShapeSuite:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode


SHAPES: Dict[str, ShapeSuite] = {
    "train_4k": ShapeSuite("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSuite("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSuite("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSuite("long_500k", 524288, 1, "decode"),
}

# Archs whose attention cost is sub-quadratic / O(1)-state at decode time.
LONG_CONTEXT_ARCHS = {"mamba2-780m", "recurrentgemma-9b", "gemma3-1b"}


def cell_supported(arch: str, shape_name: str) -> Tuple[bool, str]:
    """Is this (arch × shape) cell in contract?  Returns (ok, reason)."""
    if shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return False, ("pure full-attention arch: 524k decode requires "
                       "sub-quadratic attention (DESIGN.md skip list)")
    return True, ""


def _arr(shape, dtype, concrete: bool, rng: Optional[np.random.Generator],
         low=0, high=2):
    if not concrete:
        return jax.ShapeDtypeStruct(shape, dtype)
    rng = rng or np.random.default_rng(0)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.integers(low, high, size=shape), dtype)
    return jnp.asarray(rng.normal(size=shape) * 0.02, dtype)


def train_batch_specs(cfg: ModelConfig, seq: int, batch: int,
                      concrete: bool = False,
                      rng: Optional[np.random.Generator] = None
                      ) -> Dict[str, Any]:
    """Inputs for train_step / prefill.  seq is the *total* sequence."""
    dt = jnp.dtype(cfg.dtype)
    v = cfg.vocab
    out: Dict[str, Any] = {}
    if cfg.family == "vlm":
        img = min(cfg.img_tokens, seq // 2)
        text = seq - img
        out["tokens"] = _arr((batch, text), jnp.int32, concrete, rng,
                             high=v)
        out["targets"] = _arr((batch, text), jnp.int32, concrete, rng,
                              high=v)
        out["img_embeds"] = _arr((batch, img, cfg.d_model), dt, concrete,
                                 rng)
        if concrete:
            # stub M-RoPE ids: all three components advance with position
            # (text behaviour; image rows/cols would diverge in h/w comps)
            pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32),
                                   (3, batch, seq))
            out["positions"] = pos
        else:
            out["positions"] = _arr((3, batch, seq), jnp.int32, concrete,
                                    rng, high=seq)
    elif cfg.family == "encdec":
        out["tokens"] = _arr((batch, seq), jnp.int32, concrete, rng, high=v)
        out["targets"] = _arr((batch, seq), jnp.int32, concrete, rng,
                              high=v)
        out["enc_frames"] = _arr((batch, cfg.enc_seq, cfg.d_model), dt,
                                 concrete, rng)
        out["enc_len"] = _arr((batch,), jnp.int32, concrete, rng,
                              low=cfg.enc_seq, high=cfg.enc_seq + 1)
    else:
        out["tokens"] = _arr((batch, seq), jnp.int32, concrete, rng, high=v)
        out["targets"] = _arr((batch, seq), jnp.int32, concrete, rng,
                              high=v)
    return out


def prefill_batch_specs(cfg: ModelConfig, seq: int, batch: int,
                        concrete: bool = False,
                        rng: Optional[np.random.Generator] = None):
    b = train_batch_specs(cfg, seq, batch, concrete, rng)
    b.pop("targets", None)
    return b


def decode_specs(cfg: ModelConfig, seq: int, batch: int,
                 concrete: bool = False,
                 rng: Optional[np.random.Generator] = None
                 ) -> Dict[str, Any]:
    """Inputs for serve_step: one new token against a cache of ``seq``."""
    return {
        "tokens": _arr((batch, 1), jnp.int32, concrete, rng,
                       high=cfg.vocab),
        "pos": (jnp.asarray(seq - 1, jnp.int32) if concrete
                else jax.ShapeDtypeStruct((), jnp.int32)),
    }


def input_specs(cfg: ModelConfig, shape_name: str, concrete: bool = False,
                rng: Optional[np.random.Generator] = None):
    """(step_kind, batch-pytree) for one assigned cell."""
    s = SHAPES[shape_name]
    if s.kind == "train":
        return "train", train_batch_specs(cfg, s.seq_len, s.global_batch,
                                          concrete, rng)
    if s.kind == "prefill":
        return "prefill", prefill_batch_specs(cfg, s.seq_len,
                                              s.global_batch, concrete, rng)
    return "decode", decode_specs(cfg, s.seq_len, s.global_batch,
                                  concrete, rng)
