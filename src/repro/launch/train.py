"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch opt-tiny --steps 200 \
        --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

Runs on whatever devices exist (local mesh); the same step function lowers
onto the production mesh via dryrun.py. Integrates: deterministic pipeline,
AdamW, sharded checkpointing (async), straggler watchdog, resilient restart.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.pipeline import DataConfig, make_pipeline
from repro.dist.fault import StepWatchdog, run_resilient
from repro.ckpt.checkpoint import CheckpointManager, latest_step
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim.adamw import AdamWConfig, adamw_init


def train(arch: str = "opt-tiny", steps: int = 100, batch: int = 8, seq: int = 256,
          lr: float = 3e-4, ckpt_dir: str = None, save_every: int = 50,
          reduced: bool = True, log_every: int = 10, seed: int = 0,
          params=None, cfg=None):
    cfg = cfg or (get_config(arch).reduced() if reduced else get_config(arch))
    if seq > cfg.max_seq_len:
        cfg = dataclasses.replace(cfg, max_seq_len=seq)
    opt_cfg = AdamWConfig(lr=lr, total_steps=steps, warmup_steps=max(steps // 20, 5))
    step_fn = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))

    key = jax.random.PRNGKey(seed)
    params = params if params is not None else init_params(key, cfg)
    opt_state = adamw_init(params)

    data_cfg = DataConfig(seq_len=seq, global_batch=batch, vocab_size=cfg.vocab_size)
    batch_at = make_pipeline(data_cfg)

    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    if ckpt and latest_step(ckpt.dir) is not None:
        (params, opt_state), manifest = ckpt.restore()
        start = manifest["step"]
        print(f"[train] resumed from step {start}")

    watchdog = StepWatchdog()
    losses = []

    def one_step(state, step):
        p, o = state
        tokens = jnp.asarray(batch_at(step))
        p, o, metrics = step_fn(p, o, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step={step} loss={losses[-1]:.4f} "
                  f"lr={float(metrics['lr']):.2e} gnorm={float(metrics['grad_norm']):.2f}",
                  flush=True)
        return (p, o)

    t0 = time.monotonic()
    if ckpt:
        state, events = run_resilient(one_step, (params, opt_state), n_steps=steps,
                                      ckpt=ckpt, save_every=save_every,
                                      start_step=start, watchdog=watchdog)
        params, opt_state = state
    else:
        state = (params, opt_state)
        for s in range(start, steps):
            state = one_step(state, s)
        params, opt_state = state
    dt = time.monotonic() - t0
    print(f"[train] {steps - start} steps in {dt:.1f}s "
          f"({(steps - start) / max(dt, 1e-9):.2f} it/s); straggler flags: {watchdog.flagged}")
    return params, losses, cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-tiny")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--full", action="store_true", help="full-size config (not reduced)")
    args = ap.parse_args()
    setup_compile_cache()
    train(args.arch, args.steps, args.batch, args.seq, args.lr, args.ckpt_dir,
          args.save_every, reduced=not args.full)


if __name__ == "__main__":
    main()
