"""Serving driver: prefill a batch of prompts, then greedy decode.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
        --batch 4 --prompt-len 32 --gen 16

On a real pod the params/caches carry the shardings of
parallel/sharding.py.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs import shapes as sh
from repro.models.model import build_model
from repro.serve.engine import ServeEngine


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=configs.ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))
    engine = ServeEngine(model, params,
                         max_len=args.prompt_len + args.gen + 8)

    rng = np.random.default_rng(args.seed)
    batch = sh.prefill_batch_specs(cfg, args.prompt_len, args.batch,
                                   concrete=True, rng=rng)
    t0 = time.perf_counter()
    state = engine.prefill(batch)
    t_prefill = time.perf_counter() - t0
    toks, state = engine.generate(state, steps=args.gen)
    t_decode = time.perf_counter() - t0 - t_prefill
    out = np.asarray(toks)
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"prefill={t_prefill*1e3:.1f}ms "
          f"decode={t_decode/args.gen*1e3:.2f}ms/tok")
    print(f"[serve] generated tokens[0] = {out[0].tolist()}")
    return {"tokens": out, "prefill_s": t_prefill,
            "decode_s_per_tok": t_decode / args.gen}


if __name__ == "__main__":
    main()
