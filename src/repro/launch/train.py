"""End-to-end training driver.

Drives ``make_train_step`` with the full substrate stack: synthetic data
pipeline with prefetch, AdamW + schedule, async atomic checkpoints,
auto-resume and straggler monitoring (runtime/fault.py).  Works on a
single CPU device (reduced configs) and on a mesh (full configs).

The paper integration: with ``--grad-sync nap|rd|smp|auto`` the scalar
metrics and (in pure-DP mode) the gradient buckets are synchronised with
the explicit NAP/baseline collectives instead of XLA's default psum —
exercised end-to-end by examples/train_lm.py and the integration tests.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \\
      --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager
from ..configs import SHAPES, get_config, reduced
from ..configs.base import OptimizerConfig, TrainConfig
from ..data import Prefetcher, SyntheticLM
from ..models import build_model
from ..optim import adamw_init
from ..runtime import ResumableLoop, StragglerMonitor, enable_compile_cache
from .mesh import dp_axes as mesh_dp_axes
from .steps import make_policy, make_train_step

log = logging.getLogger("repro.train")


def build_training(
    cfg,
    train_cfg: TrainConfig,
    *,
    mesh=None,
    ckpt_dir: str | Path,
):
    """Assemble (loop, data, step_fn) for a config. Returns the loop."""
    policy = make_policy(cfg, mesh)
    model = build_model(cfg, policy)

    data = SyntheticLM(
        vocab_size=cfg.vocab_size,
        seq_len=train_cfg.seq_len,
        global_batch=train_cfg.global_batch,
        seed=train_cfg.seed,
        mesh=mesh,
        batch_axes=mesh_dp_axes(mesh) if mesh is not None else (),
    )

    n_micro = 1
    if train_cfg.microbatch:
        n_micro = train_cfg.global_batch // train_cfg.microbatch
    train_step = make_train_step(
        model, train_cfg.optimizer, n_micro=n_micro
    )
    jit_step = jax.jit(train_step, donate_argnums=(0,))

    def make_state():
        params = jax.jit(model.init)(jax.random.PRNGKey(train_cfg.seed))
        if mesh is not None:
            params = policy.shard_params(params)
        opt = adamw_init(
            params, moment_dtype=train_cfg.optimizer.moment_dtype
        )
        return {"params": params, "opt": opt}

    def step_fn(state, step):
        batch = data.batch(step)
        state, metrics = jit_step(state, batch)
        return state, {
            k: float(v) for k, v in metrics.items() if jnp.ndim(v) == 0
        }

    ckpt = CheckpointManager(
        ckpt_dir, keep=train_cfg.keep_checkpoints, async_save=True
    )
    loop = ResumableLoop(
        step_fn=step_fn,
        make_state=make_state,
        ckpt=ckpt,
        checkpoint_every=train_cfg.checkpoint_every,
        monitor=StragglerMonitor(),
    )
    return loop


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="same-family miniature config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--microbatch", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    train_cfg = TrainConfig(
        steps=args.steps,
        seq_len=args.seq,
        global_batch=args.batch,
        microbatch=args.microbatch,
        checkpoint_every=args.ckpt_every,
        optimizer=OptimizerConfig(
            lr=args.lr,
            schedule=args.schedule,
            warmup_steps=max(5, args.steps // 10),
            decay_steps=args.steps,
        ),
    )
    loop = build_training(cfg, train_cfg, ckpt_dir=args.ckpt_dir)
    t0 = time.time()
    loop.run(args.steps)
    losses = [m["loss"] for m in loop.metrics_log]
    if losses:
        print(
            f"steps={len(losses)} first_loss={losses[0]:.4f} "
            f"last_loss={losses[-1]:.4f} wall_s={time.time()-t0:.1f} "
            f"stragglers={len(loop.monitor.events)}"
        )


if __name__ == "__main__":
    main()
