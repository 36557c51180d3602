"""Config-driven model assembly for all assigned architectures.

Layers are organized as **period-scan**: the per-layer kind pattern
(e.g. recurrentgemma's (rglru, rglru, local), gemma3's (local×5, attn))
repeats for ``n_periods`` via one ``jax.lax.scan`` over stacked parameters
— 61-layer Kimi compiles as one scan body — with remainder layers
("head": kimi's first dense layer; "tail": pattern leftovers) unrolled.
Remat policy wraps the scan body.

Families:
  dense / moe / vlm : decoder-only LM (attention per pattern kind; MLP or
                      MoE feed-forward)
  ssm               : mamba2 blocks (no separate FFN)
  hybrid            : recurrentgemma temporal pattern + MLP every block
  encdec            : whisper — encoder stack over stubbed audio-frame
                      embeddings + decoder with cross-attention

Public entry points (all pure; see launch/ for pjit wrappers):
  init(key)                          -> params
  loss_fn(params, batch)             -> (loss, metrics)
  prefill(params, batch, cache)      -> (logits, cache)
  decode_step(params, tokens, cache, pos) -> (logits, cache)
  init_cache(batch_size, max_len)    -> cache pytree
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S

Params = Any


# ===================================================================== blocks
def _block_init(key, cfg: ModelConfig, kind: str, moe: bool,
                cross: bool = False) -> Params:
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": L.rmsnorm_init(cfg.d_model, dt)}
    if kind in ("attn", "local"):
        p["attn"] = A.attn_init(keys[0], cfg)
    elif kind == "rglru":
        p["rglru"] = R.rglru_init(keys[0], cfg)
    elif kind == "ssm":
        p["ssm"] = S.ssm_init(keys[0], cfg)
        return p                                   # mamba2: mixer only
    else:
        raise ValueError(kind)
    if cross:
        p["normx"] = L.rmsnorm_init(cfg.d_model, dt)
        p["xattn"] = A.attn_init(keys[2], cfg, cross=True)
    p["norm2"] = L.rmsnorm_init(cfg.d_model, dt)
    if moe:
        p["moe"] = M.moe_init(keys[1], cfg)
    else:
        p["mlp"] = L.mlp_init(keys[1], cfg, cfg.d_ff)
    return p


def _block_apply_train(p, cfg: ModelConfig, kind: str, h, positions,
                       enc_out=None, enc_len=None, cache=None):
    """One block, full-sequence. Returns (h, aux, cache-or-None).

    When ``cache`` is given (prefill), the mixer's K/V (or recurrent state)
    is written into it using decode-compatible addressing."""
    aux = jnp.zeros((), jnp.float32)
    x = L.rmsnorm(p["norm1"], h, cfg.norm_eps)
    if kind in ("attn", "local"):
        if cache is not None:
            y, (k, v) = A.attend_train(p["attn"], cfg, x, positions,
                                       kind=kind, return_kv=True)
            ck, cv = A.fill_kv_cache(cache["k"], cache["v"], k, v, kind,
                                     cfg.window)
            cache = dict(cache, k=ck, v=cv)
            h = h + y
        else:
            h = h + A.attend_train(p["attn"], cfg, x, positions, kind=kind)
    elif kind == "rglru":
        if cache is not None:
            y, st = R.rglru_apply_train(p["rglru"], cfg, x,
                                        return_state=True)
            cache = dict(cache, **st)
            h = h + y
        else:
            h = h + R.rglru_apply_train(p["rglru"], cfg, x)
    elif kind == "ssm":
        if cache is not None:
            y, st = S.ssm_apply_train(p["ssm"], cfg, x, return_state=True)
            return h + y, aux, dict(cache, **st)
        return h + S.ssm_apply_train(p["ssm"], cfg, x), aux, None
    if "xattn" in p:
        xx = L.rmsnorm(p["normx"], h, cfg.norm_eps)
        h = h + A.attend_train(p["xattn"], cfg, xx, None, kind="cross",
                               enc_out=enc_out, enc_len=enc_len)
        if cache is not None:
            xk = (enc_out @ p["xattn"]["wk"]).reshape(
                enc_out.shape[0], enc_out.shape[1], cfg.n_kv_heads,
                cfg.head_dim)
            xv = (enc_out @ p["xattn"]["wv"]).reshape(
                enc_out.shape[0], enc_out.shape[1], cfg.n_kv_heads,
                cfg.head_dim)
            cache = dict(cache, xk=xk, xv=xv)
    if "norm2" not in p:                 # mamba2 blocks have no FFN
        return h, aux, cache
    x2 = L.rmsnorm(p["norm2"], h, cfg.norm_eps)
    if "moe" in p:
        y, aux = M.moe_apply(p["moe"], cfg, x2)
        h = h + y
    else:
        h = h + L.mlp_apply(p["mlp"], x2, cfg.mlp_kind)
    return h, aux, cache


def _block_cache_init(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                      cross: bool = False):
    dt = jnp.dtype(cfg.dtype)
    if kind in ("attn", "local"):
        c = min(cfg.window, max_len) if (kind == "local" and cfg.window)\
            else max_len
        cache = {"k": jnp.zeros((batch, c, cfg.n_kv_heads, cfg.head_dim),
                                dt),
                 "v": jnp.zeros((batch, c, cfg.n_kv_heads, cfg.head_dim),
                                dt)}
    elif kind == "rglru":
        cache = R.rglru_decode_init(cfg, batch, dt)
    elif kind == "ssm":
        cache = S.ssm_decode_init(cfg, batch, dt)
    else:
        raise ValueError(kind)
    if cross:
        cache["xk"] = jnp.zeros((batch, cfg.enc_seq, cfg.n_kv_heads,
                                 cfg.head_dim), dt)
        cache["xv"] = jnp.zeros((batch, cfg.enc_seq, cfg.n_kv_heads,
                                 cfg.head_dim), dt)
    return cache


def _block_apply_decode(p, cfg: ModelConfig, kind: str, h, cache, pos,
                        positions=None, enc_len=None):
    """One block, single token. Returns (h, cache)."""
    x = L.rmsnorm(p["norm1"], h, cfg.norm_eps)
    if kind in ("attn", "local"):
        y, ck, cv = A.attend_decode(p["attn"], cfg, x, cache["k"],
                                    cache["v"], pos, kind=kind,
                                    positions=positions)
        h = h + y
        cache = dict(cache, k=ck, v=cv)
    elif kind == "rglru":
        y, cc = R.rglru_apply_decode(p["rglru"], cfg, x, cache)
        h = h + y
        cache = dict(cache, **cc)
    elif kind == "ssm":
        y, cc = S.ssm_apply_decode(p["ssm"], cfg, x, cache)
        return h + y, dict(cache, **cc)
    if "xattn" in p:
        xx = L.rmsnorm(p["normx"], h, cfg.norm_eps)
        h = h + A.attend_decode_cross(p["xattn"], cfg, xx, cache["xk"],
                                      cache["xv"], enc_len)
    x2 = L.rmsnorm(p["norm2"], h, cfg.norm_eps)
    if "moe" in p:
        # drop-free capacity at decode: a one-token step must keep its experts
        y, _ = M.moe_apply(p["moe"], cfg, x2,
                           capacity_factor=float(cfg.n_experts))
        h = h + y
    else:
        h = h + L.mlp_apply(p["mlp"], x2, cfg.mlp_kind)
    return h, cache


# ==================================================================== model
@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------ structure
    @property
    def pattern(self) -> Tuple[str, ...]:
        return self.cfg.layer_pattern

    @property
    def n_head_layers(self) -> int:
        return self.cfg.first_k_dense

    @property
    def n_scan_layers(self) -> int:
        return ((self.cfg.n_layers - self.n_head_layers)
                // len(self.pattern)) * len(self.pattern)

    @property
    def n_periods(self) -> int:
        return self.n_scan_layers // len(self.pattern)

    def tail_kinds(self) -> Tuple[str, ...]:
        n_tail = self.cfg.n_layers - self.n_head_layers - self.n_scan_layers
        return tuple(self.pattern[i % len(self.pattern)]
                     for i in range(n_tail))

    def _is_moe(self, scan_or_tail: bool) -> bool:
        return self.cfg.family == "moe"

    @property
    def _cross(self) -> bool:
        return self.cfg.family == "encdec"

    # ----------------------------------------------------------------- init
    def init(self, key) -> Params:
        cfg = self.cfg
        k_embed, k_head, k_scan, k_tail, k_enc = jax.random.split(key, 5)
        params: Dict[str, Any] = {"embed": L.embed_init(k_embed, cfg)}

        # head layers (kimi-k2 first dense layer): unrolled, dense MLP
        head = []
        for i, kk in enumerate(jax.random.split(k_head,
                                                max(self.n_head_layers, 1))):
            if i >= self.n_head_layers:
                break
            head.append(_block_init(kk, cfg, "attn", moe=False))
        params["head_blocks"] = head

        # scanned periods: stacked params per pattern position
        scan_blocks = []
        moe = self.cfg.family == "moe"
        if self.n_periods > 0:
            for pos, kind in enumerate(self.pattern):
                keys = jax.random.split(
                    jax.random.fold_in(k_scan, pos), self.n_periods)
                per = [_block_init(keys[i], cfg, kind, moe=moe,
                                   cross=self._cross)
                      for i in range(self.n_periods)]
                scan_blocks.append(jax.tree.map(
                    lambda *xs: jnp.stack(xs), *per))
        params["scan_blocks"] = scan_blocks

        # tail layers: unrolled
        tail = []
        tkinds = self.tail_kinds()
        for i, kk in enumerate(jax.random.split(k_tail,
                                                max(len(tkinds), 1))):
            if i >= len(tkinds):
                break
            tail.append(_block_init(kk, cfg, tkinds[i], moe=moe,
                                    cross=self._cross))
        params["tail_blocks"] = tail

        params["final_norm"] = L.rmsnorm_init(cfg.d_model,
                                              jnp.dtype(cfg.dtype))
        if cfg.family == "encdec":
            enc = []
            for kk in jax.random.split(k_enc, cfg.enc_layers):
                enc.append(_block_init(kk, cfg, "attn", moe=False))
            params["encoder"] = enc
        return params

    def init_eval(self) -> Params:
        """Abstract init (ShapeDtypeStructs), for sharding specs."""
        return jax.eval_shape(self.init, jax.random.key(0))

    # ------------------------------------------------------------- forward
    def _embed_inputs(self, params, batch):
        """Token + modality-stub embedding.  Returns (h, positions)."""
        cfg = self.cfg
        h = L.embed_tokens(params["embed"], batch["tokens"])
        if cfg.family == "vlm":
            # image patch embeddings (stub frontend) prepended
            h = jnp.concatenate([batch["img_embeds"].astype(h.dtype), h],
                                axis=1)
            positions = batch["positions"]            # (3, B, S) M-RoPE
        else:
            b, s = h.shape[0], h.shape[1]
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                         (b, s))
        return h, positions

    def _encode(self, params, batch):
        """Whisper encoder over stubbed frame embeddings (B, T, d)."""
        cfg = self.cfg
        h = batch["enc_frames"].astype(jnp.dtype(cfg.dtype))
        pos_tab = jnp.asarray(L.sinusoid_positions(h.shape[1], cfg.d_model),
                              h.dtype)
        h = h + pos_tab[None]
        for p in params["encoder"]:
            x = L.rmsnorm(p["norm1"], h, cfg.norm_eps)
            q, k, v = A._project_qkv(p["attn"], cfg, x, x, None, None)
            att = A.blockwise_attention(q, k, v, causal=False)
            b, s = x.shape[:2]
            h = h + att.reshape(b, s, cfg.q_dim) @ p["attn"]["wo"]
            x2 = L.rmsnorm(p["norm2"], h, cfg.norm_eps)
            h = h + L.mlp_apply(p["mlp"], x2, cfg.mlp_kind)
        return h

    def _remat(self, fn):
        if self.cfg.remat == "none":
            return fn
        if self.cfg.remat == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots
            return jax.checkpoint(fn, policy=policy)
        return jax.checkpoint(fn)

    def forward(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence forward.  Returns (logits_f32, aux_loss)."""
        cfg = self.cfg
        h, positions = self._embed_inputs(params, batch)
        enc_out = self._encode(params, batch) if cfg.family == "encdec" \
            else None
        enc_len = batch.get("enc_len") if cfg.family == "encdec" else None
        aux = jnp.zeros((), jnp.float32)

        for p in params["head_blocks"]:
            h, a, _ = _block_apply_train(p, cfg, "attn", h, positions)
            aux = aux + a

        pattern = self.pattern

        def period_body(carry, xs):
            h, aux = carry
            for pos, kind in enumerate(pattern):
                h, a, _ = _block_apply_train(xs[pos], cfg, kind, h,
                                             positions, enc_out=enc_out,
                                             enc_len=enc_len)
                aux = aux + a
            return (h, aux), None

        if self.n_periods > 0:
            body = self._remat(period_body)
            (h, aux), _ = jax.lax.scan(body, (h, aux),
                                       tuple(params["scan_blocks"]))

        for p, kind in zip(params["tail_blocks"], self.tail_kinds()):
            h, a, _ = _block_apply_train(p, cfg, kind, h, positions,
                                         enc_out=enc_out, enc_len=enc_len)
            aux = aux + a

        h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = L.lm_logits(params["embed"], h, cfg.tie_embeddings,
                             out_dtype=jnp.dtype(cfg.logits_dtype),
                             true_vocab=cfg.vocab)
        return logits, aux

    # -------------------------------------------------------------- prefill
    def prefill(self, params, batch, max_len: int):
        """Process a full prompt, returning (last_logits, filled cache).

        The cache is decode-compatible: ``decode_step`` continues from
        position S.  batch needs 'tokens' (+ modality stubs)."""
        cfg = self.cfg
        h, positions = self._embed_inputs(params, batch)
        b = h.shape[0]
        enc_out = self._encode(params, batch) if cfg.family == "encdec" \
            else None
        enc_len = batch.get("enc_len") if cfg.family == "encdec" else None
        cache = self.init_cache(b, max_len)

        new_head = []
        for p, c in zip(params["head_blocks"], cache["head"]):
            h, _, c = _block_apply_train(p, cfg, "attn", h, positions,
                                         cache=c)
            new_head.append(c)

        pattern = self.pattern

        def period_body(carry, xs):
            h = carry
            blocks, caches = xs
            new_caches = []
            for pos, kind in enumerate(pattern):
                h, _, c = _block_apply_train(
                    blocks[pos], cfg, kind, h, positions, enc_out=enc_out,
                    enc_len=enc_len, cache=caches[pos])
                new_caches.append(c)
            return h, tuple(new_caches)

        new_scan = cache["scan"]
        if self.n_periods > 0:
            h, new_scan = jax.lax.scan(
                self._remat(period_body), h,
                (tuple(params["scan_blocks"]), tuple(cache["scan"])))
            new_scan = list(new_scan)

        new_tail = []
        for p, c, kind in zip(params["tail_blocks"], cache["tail"],
                              self.tail_kinds()):
            h, _, c = _block_apply_train(p, cfg, kind, h, positions,
                                         enc_out=enc_out, enc_len=enc_len,
                                         cache=c)
            new_tail.append(c)

        h = L.rmsnorm(params["final_norm"], h[:, -1:], cfg.norm_eps)
        logits = L.lm_logits(params["embed"], h, cfg.tie_embeddings,
                             out_dtype=jnp.dtype(cfg.logits_dtype),
                             true_vocab=cfg.vocab)
        cache = dict(cache, head=new_head, scan=new_scan, tail=new_tail)
        if cfg.family == "encdec":
            cache["enc_len"] = jnp.full((b,), enc_out.shape[1], jnp.int32)
        return logits[:, 0], cache

    # ---------------------------------------------------------------- loss
    def loss_fn(self, params, batch) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        logits, aux = self.forward(params, batch)
        targets = batch["targets"]
        if cfg.family == "vlm":
            # image positions carry no next-token loss
            pad = jnp.zeros(
                (targets.shape[0], batch["img_embeds"].shape[1]),
                targets.dtype)
            targets = jnp.concatenate([pad, targets], axis=1)
            mask = jnp.concatenate(
                [jnp.zeros_like(pad, jnp.float32),
                 jnp.ones_like(batch["targets"], jnp.float32)], axis=1)
        else:
            mask = batch.get("loss_mask",
                             jnp.ones_like(targets, jnp.float32))
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None],
                                   axis=-1)[..., 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = (nll * mask).sum() / denom
        loss = ce + aux
        return loss, {"ce": ce, "aux": aux,
                      "ppl_proxy": jnp.exp(jnp.minimum(ce, 20.0))}

    # --------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        cache: Dict[str, Any] = {
            "head": [_block_cache_init(cfg, "attn", batch, max_len)
                     for _ in range(self.n_head_layers)],
            "tail": [_block_cache_init(cfg, k, batch, max_len,
                                       cross=self._cross)
                     for k in self.tail_kinds()],
        }
        scan = []
        for kind in self.pattern:
            per = [_block_cache_init(cfg, kind, batch, max_len,
                                     cross=self._cross)
                   for _ in range(self.n_periods)]
            scan.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per)
                        if per else [])
        cache["scan"] = scan
        if cfg.family == "encdec":
            cache["enc_len"] = jnp.zeros((batch,), jnp.int32)
        return cache

    # -------------------------------------------------------------- decode
    def decode_positions(self, pos, batch: int):
        """Positions pytree for one decode step at absolute ``pos``."""
        if self.cfg.mrope:
            p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                 (3, batch, 1))
            return p
        return None

    def decode_step(self, params, tokens, cache, pos, enc_out=None):
        """tokens (B, 1) int32; pos () int32 absolute position.

        Returns (logits (B, vocab) f32, cache)."""
        cfg = self.cfg
        b = tokens.shape[0]
        h = L.embed_tokens(params["embed"], tokens)
        positions = self.decode_positions(pos, b)
        enc_len = cache.get("enc_len") if cfg.family == "encdec" else None

        new_head = []
        for p, c in zip(params["head_blocks"], cache["head"]):
            h, c = _block_apply_decode(p, cfg, "attn", h, c, pos)
            new_head.append(c)

        pattern = self.pattern

        def period_body(carry, xs):
            h = carry
            blocks, caches = xs
            new_caches = []
            for i, kind in enumerate(pattern):
                h, c = _block_apply_decode(blocks[i], cfg, kind, h,
                                           caches[i], pos,
                                           positions=positions,
                                           enc_len=enc_len)
                new_caches.append(c)
            return h, tuple(new_caches)

        new_scan = cache["scan"]
        if self.n_periods > 0:
            h, new_scan = jax.lax.scan(
                period_body, h,
                (tuple(params["scan_blocks"]), tuple(cache["scan"])))
            new_scan = list(new_scan)

        new_tail = []
        for p, c, kind in zip(params["tail_blocks"], cache["tail"],
                              self.tail_kinds()):
            h, c = _block_apply_decode(p, cfg, kind, h, c, pos,
                                       positions=positions, enc_len=enc_len)
            new_tail.append(c)

        h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = L.lm_logits(params["embed"], h, cfg.tie_embeddings,
                             out_dtype=jnp.dtype(cfg.logits_dtype),
                             true_vocab=cfg.vocab)
        new_cache = dict(cache, head=new_head, scan=new_scan, tail=new_tail)
        return logits[:, 0], new_cache

    def encode_for_decode(self, params, batch, cache):
        """Whisper: run the encoder, fill cross-attn K/V caches."""
        cfg = self.cfg
        enc = self._encode(params, batch)

        def fill(p, c):
            k = (enc @ p["xattn"]["wk"]).reshape(
                enc.shape[0], enc.shape[1], cfg.n_kv_heads, cfg.head_dim)
            v = (enc @ p["xattn"]["wv"]).reshape(
                enc.shape[0], enc.shape[1], cfg.n_kv_heads, cfg.head_dim)
            return dict(c, xk=k, xv=v)

        cache = dict(cache)
        cache["tail"] = [fill(p, c) for p, c in
                         zip(params["tail_blocks"], cache["tail"])]
        new_scan = []
        for pos in range(len(self.pattern)):
            blocks = params["scan_blocks"][pos]
            caches = cache["scan"][pos]
            filled = jax.vmap(fill)(blocks, caches)
            new_scan.append(filled)
        cache["scan"] = new_scan
        cache["enc_len"] = jnp.full((enc.shape[0],), enc.shape[1],
                                    jnp.int32)
        return cache


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
