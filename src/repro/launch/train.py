"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --smoke --steps 100 --batch 8 --seq 128 --spin-ingest

Wires together: config registry → model → (optional) mesh + shardings →
AdamW → packetized SLMP/DDT data pipeline with SpinIngest (the paper's
offloaded datatype processing) double-buffered against the train step →
atomic checkpoints → fault supervisor with bounded restarts.

``--smoke`` selects the reduced same-family config (CPU-runnable);
omitting it uses the full assigned architecture (real-cluster scale).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.core import overlap as ovl
from repro.launch import faults
from repro.models.model import build_model
from repro.train import checkpoint as ckpt
from repro.train import data as datalib
from repro.train import optimizer as opt
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    choices=configs.ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default="/tmp/repro-train-ckpt")
    ap.add_argument("--spin-ingest", action="store_true",
                    help="feed training through the packetized SLMP/DDT "
                         "sPIN pipeline (paper §V-C) with overlap")
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    model = build_model(cfg)
    print(f"[train] arch={cfg.name} params~{cfg.param_count():,} "
          f"steps={args.steps} batch={args.batch} seq={args.seq} "
          f"spin_ingest={args.spin_ingest}")

    ocfg = opt.OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                         total_steps=args.steps)
    tcfg = TrainerConfig(steps=args.steps, microbatches=args.microbatches,
                         log_every=max(args.steps // 20, 1),
                         ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir, donate=False)

    def make_state():
        params = model.init(jax.random.key(args.seed))
        return params, opt.init(params)

    def run(state, attempt):
        params, ost = state
        trainer = Trainer(model, ocfg, tcfg)
        if args.spin_ingest:
            pipe = datalib.PacketizedPipeline(
                vocab=cfg.vocab, batch=args.batch, seq=args.seq,
                seed=args.seed)
            ingest = datalib.SpinIngest(pipe)
            feeds = datalib.prefetch_iterator(pipe, args.steps)
            # double-buffered: ingest t+1 overlaps train step t
            step_fn = trainer.build_step()
            t_mm = t_poll = 0.0
            params_, ost_ = params, ost
            batch = ingest(next(feeds))
            hist = []
            for i, feed in enumerate(feeds):
                params_, ost_, metrics = step_fn(params_, ost_, batch)
                nxt = ingest(feed)                     # overlaps step
                t0 = time.perf_counter()
                jax.block_until_ready(metrics["loss"])
                t1 = time.perf_counter()
                jax.block_until_ready(nxt)
                t2 = time.perf_counter()
                t_mm += t1 - t0
                t_poll += t2 - t1
                batch = nxt
                if (i + 1) % tcfg.log_every == 0:
                    hist.append({"step": i + 1,
                                 "loss": float(metrics["loss"])})
                    print(f"  step {i+1:5d} loss "
                          f"{float(metrics['loss']):.4f}")
                if tcfg.ckpt_every and (i + 1) % tcfg.ckpt_every == 0:
                    ckpt.save(tcfg.ckpt_dir, i + 1, (params_, ost_))
            r = t_mm / max(t_mm + t_poll, 1e-12)
            print(f"[train] overlap ratio R = {r:.4f} "
                  f"(t_train={t_mm:.2f}s t_poll={t_poll:.2f}s)")
            return {"history": hist, "overlap_ratio": r}
        else:
            corpus = datalib.SyntheticCorpus(cfg.vocab, seed=args.seed)

            def batches():
                import jax.numpy as jnp
                for i in range(args.steps):
                    toks = corpus.batch(i, args.batch, args.seq)
                    yield {"tokens": jnp.asarray(toks[:, :-1]),
                           "targets": jnp.asarray(toks[:, 1:])}

            p2, o2, hist = trainer.fit(params, ost, batches(),
                                       resume=attempt > 0)
            for h in hist[-3:]:
                print(f"  step {h['step']:5d} loss {h['loss']:.4f}")
            return {"history": hist,
                    "stragglers": trainer.straggler_events}

    result, report = faults.run_with_restarts(
        make_state, run, max_restarts=args.max_restarts)
    if not report.succeeded:
        raise SystemExit(f"training failed after {report.restarts} "
                         f"restarts: {report.errors}")
    print(f"[train] done (restarts={report.restarts})")
    return result


if __name__ == "__main__":
    main()
