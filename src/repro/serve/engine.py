"""Serving engine: prefill + greedy decode over the model zoo's caches.

Jitted once per (model, batch, max_len); decode donates the cache (in-place
on device).  This is the single-host form of the engine; a multi-chip
form takes its cache shardings from parallel/sharding.py (incl.
sequence-sharded long-context caches).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.model import Model


@dataclasses.dataclass
class ServeState:
    cache: Any
    last_tokens: jax.Array      # (B, 1)
    pos: jax.Array              # () int32 — next position to write


class ServeEngine:
    def __init__(self, model: Model, params, max_len: int):
        self.model = model
        self.params = params
        self.max_len = max_len
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, max_len=max_len))

        def _decode(p, tokens, cache, pos):
            logits, cache2 = model.decode_step(p, tokens, cache, pos)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt[:, None], cache2

        self._decode = jax.jit(_decode, donate_argnums=(2,))

    def prefill(self, batch: Dict[str, Any]) -> ServeState:
        logits, cache = self._prefill(self.params, batch)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        prompt_len = batch["tokens"].shape[1]
        if self.model.cfg.family == "vlm":
            prompt_len += batch["img_embeds"].shape[1]
        return ServeState(cache=cache, last_tokens=first,
                          pos=jnp.asarray(prompt_len, jnp.int32))

    def step(self, state: ServeState) -> Tuple[jax.Array, ServeState]:
        nxt, cache = self._decode(self.params, state.last_tokens,
                                  state.cache, state.pos)
        return nxt, ServeState(cache=cache, last_tokens=nxt,
                               pos=state.pos + 1)

    def generate(self, state: ServeState, steps: int):
        toks = [state.last_tokens]
        for _ in range(steps - 1):
            nxt, state = self.step(state)
            toks.append(nxt)
        return jnp.concatenate(toks, axis=1), state
