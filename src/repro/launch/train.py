"""End-to-end training driver (CPU-runnable) with checkpoint/restart.

Trains a small-profile LM with the BMMC-shuffled data pipeline, periodic
integrity-checked checkpoints, and automatic resume — the single-host
version of the fault-tolerance story in DESIGN.md §5 (on a cluster, each
host runs this loop with its own loader shard; restore is elastic across
mesh changes).

Usage::

    python -m repro.launch.train --steps 200 --ckpt-dir /tmp/ckpt
    python -m repro.launch.train --arch mamba2-130m --profile smoke
    python -m repro.launch.train --profile 100m --steps 300   # ~100M params
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import ckpt
from ..configs import get_config, reduce_for_smoke
from ..configs.base import ArchConfig
from ..data.pipeline import DataConfig, ShardedLoader
from ..models import model as M
from ..optim.schedule import warmup_cosine
from ..train.step import init_opt, make_train_step
from .compile_cache import setup_compile_cache

PROFILES = {
    # name -> (d_model, layers, heads, d_ff, vocab)  [~params]
    "smoke": (128, 4, 4, 512, 1024),          # ~1M: CI-speed
    "20m": (384, 8, 6, 1536, 8192),           # ~20M
    "100m": (768, 12, 12, 3072, 32768),       # ~124M (GPT-2-small-like)
}


def profile_config(profile: str, base: ArchConfig = None) -> ArchConfig:
    d, l, h, f, v = PROFILES[profile]
    kw = dict(d_model=d, n_heads=h, n_kv_heads=max(h // 2, 1), d_ff=f,
              vocab_size=v, n_periods=l, head_dim=d // h,
              dtype=jnp.float32, remat=False, kv_block=256)
    if base is None:
        return ArchConfig(name=f"lm-{profile}", family="dense",
                          pattern=("dense",), **kw)
    return dataclasses.replace(base, **kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="assigned arch id (reduced); default: plain dense LM")
    ap.add_argument("--profile", default="smoke", choices=sorted(PROFILES))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    setup_compile_cache()

    if args.arch:
        cfg = reduce_for_smoke(get_config(args.arch))
    else:
        cfg = profile_config(args.profile)
    print(f"arch={cfg.name} params={cfg.n_params()/1e6:.1f}M "
          f"layers={cfg.n_layers}")

    dcfg = DataConfig(n_samples_log2=16, seq_len=args.seq,
                      vocab_size=cfg.vocab_size, seed=args.seed)
    loader = ShardedLoader(dcfg, batch_size=args.batch)

    key = jax.random.PRNGKey(args.seed)
    params = M.init(cfg, key)
    opt_state = init_opt(cfg, params)
    step_fn, opt_cfg = make_train_step(cfg)
    start = 0

    if args.ckpt_dir:
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            (params, opt_state), extra = ckpt.restore(
                args.ckpt_dir, last, (params, opt_state))
            loader.restore(extra["loader"])
            start = last
            print(f"resumed from step {last} "
                  f"(epoch={loader.epoch}, loader step={loader.step})")

    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
    losses = []
    t0 = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(loader).items()}
        lr_scale = warmup_cosine(step, warmup=20, total=args.steps)
        params, opt_state, metrics = jit_step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            tok_s = args.batch * args.seq * (step - start + 1) / max(dt, 1e-9)
            print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                  f"grad_norm {float(metrics['grad_norm']):.3f}  "
                  f"tok/s {tok_s:,.0f}")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            path = ckpt.save(args.ckpt_dir, step + 1, (params, opt_state),
                             extra_state={"loader": loader.state(),
                                          "arch": cfg.name})
            print(f"checkpointed -> {path}")
    if len(losses) >= 10:
        first, last = np.mean(losses[:5]), np.mean(losses[-5:])
        print(f"loss: {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    main()
