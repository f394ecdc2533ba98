"""Training driver with checkpoint/restart fault tolerance.

Runs REAL steps on the host devices (CPU here, TPU pod in production —
the same ``build_program`` path the dry-run validates). Synthetic data
pipeline with a checkpointed cursor: kill the process at any step and
re-launch with the same --ckpt-dir to resume bit-identically.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..configs.base import ShapeSpec
from ..models import build_model
from ..train import (CheckpointManager, SyntheticData, init_state,
                     latest_step, make_train_step, restore_checkpoint,
                     schedule_for)
from .compile_cache import enable_compile_cache

__all__ = ["main", "train"]


def train(arch: str, *, steps: int = 50, batch: int = 8, seq: int = 128,
          reduced: bool = False, ckpt_dir: str = "", save_every: int = 25,
          microbatches: int = 1, compress: bool = False,
          dtype=jnp.float32, log_every: int = 10, peak_lr: float = 3e-4,
          seed: int = 0):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, remat=True)
    shape = ShapeSpec("cli", seq, batch, "train")
    data = SyntheticData(cfg, shape, seed=seed)
    step_fn = jax.jit(make_train_step(
        model, None, microbatches=microbatches, compress=compress,
        lr_schedule=schedule_for(cfg, peak_lr=peak_lr, warmup=max(steps // 20, 1),
                                 total=steps)),
        donate_argnums=(0,))

    start = 0
    state = None
    mgr = CheckpointManager(ckpt_dir, save_every=save_every) if ckpt_dir \
        else None
    if ckpt_dir:
        last = latest_step(ckpt_dir)
        if last is not None:
            tmpl = init_state(model, jax.random.PRNGKey(seed), dtype=dtype,
                              compress=compress)
            state, cursor, _ = restore_checkpoint(ckpt_dir, last, tmpl)
            start = cursor
            print(f"[resume] restored step {last}, data cursor {cursor}")
    if state is None:
        state = init_state(model, jax.random.PRNGKey(seed), dtype=dtype,
                           compress=compress)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(state["params"]))
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"batch={batch} seq={seq} steps {start}->{steps}")

    losses = []
    t0 = time.time()
    for s in range(start, steps):
        state, metrics = step_fn(state, data.batch_at(s))
        losses.append(float(metrics["loss"]))
        if s % log_every == 0 or s == steps - 1:
            dt = time.time() - t0
            tps = (s - start + 1) * batch * seq / max(dt, 1e-9)
            print(f"  step {s:5d}  loss {losses[-1]:.4f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"({tps:,.0f} tok/s)")
        if mgr is not None:
            mgr.maybe_save(s + 1, state, data_cursor=s + 1,
                           meta={"arch": cfg.name})
    if mgr is not None:
        mgr.wait()
    return state, losses


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="smollm-135m")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--save-every", type=int, default=25)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--compress", action="store_true",
                   help="int8 EF gradient compression")
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--lr", type=float, default=3e-4)
    args = p.parse_args(argv)
    enable_compile_cache()
    train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
          reduced=args.reduced, ckpt_dir=args.ckpt_dir,
          save_every=args.save_every, microbatches=args.microbatches,
          compress=args.compress, peak_lr=args.lr,
          dtype=jnp.bfloat16 if args.bf16 else jnp.float32)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
