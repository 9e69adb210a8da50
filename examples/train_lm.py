"""End-to-end driver: train a ~100M-param LM for a few hundred steps.

Uses the full production substrate on CPU: synthetic data pipeline,
AdamW + cosine schedule, async atomic checkpoints with auto-resume, and
straggler monitoring.  A mid-run process "crash" is simulated to show
checkpoint/restart working (the loop resumes from the last checkpoint and
reaches the same final state).

The model is a 12-layer llama-style decoder (~100M params), per the
"train a ~100M model for a few hundred steps" deliverable.  Expect the
loss to drop by >1 nat in ~200 steps on the synthetic mixture.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]

``--compressed-smoke`` instead runs a short multi-device training smoke
of the packed gradient transport (int8, then packed int4 with error
feedback) on a virtual 2x4 CPU mesh — the Pallas transport kernels in
interpret mode, end to end through ``make_dp_train_step``.
"""

import argparse
import os
import shutil
import sys
import time

if "--compressed-smoke" in sys.argv:
    # must be set before jax initialises (import side effect below);
    # virtual devices exist only on the CPU backend
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    )

from repro.configs.base import (
    ModelConfig,
    OptimizerConfig,
    SubLayer,
    TrainConfig,
)
from repro.launch.train import build_training

LM_100M = ModelConfig(
    name="repro-lm-100m",
    family="dense",
    num_layers=12,
    d_model=512,
    num_heads=8,
    num_kv_heads=8,
    d_ff=2048,
    vocab_size=32_000,
    pattern=(SubLayer("attn"),),
    dtype="float32",
    remat="none",
)


def compressed_smoke(steps: int) -> None:
    """Train the reduced LM a few steps over each compressed transport:
    int8, then packed int4 with error-feedback residuals in the train
    state.  Asserts finite losses — kernels, scale agreement, EF
    threading and the planner all run for real on 8 CPU devices."""
    import jax

    from repro.configs.archs import reduced
    from repro.core import comm
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_dp_train_step
    from repro.models import build_model
    from repro.optim import adamw_init, ef_init

    mesh = make_mesh((2, 4), ("pod", "data"))
    cfg = reduced(LM_100M)
    opt_cfg = OptimizerConfig(lr=1e-3, schedule="constant", warmup_steps=1)
    model = build_model(cfg)
    params0 = jax.jit(model.init)(jax.random.PRNGKey(0))
    data = SyntheticLM(
        vocab_size=cfg.vocab_size, seq_len=64, global_batch=8, seed=0,
        mesh=mesh, batch_axes=("pod", "data"),
    )
    cases = [
        ("int8", comm.CommPolicy(algorithm="nap", mean=True, compress_bits=8)),
        (
            "int4+ef",
            comm.CommPolicy(
                algorithm="nap", mean=True, compress_bits=4,
                error_feedback=True,
            ),
        ),
    ]
    for label, policy in cases:
        step = jax.jit(make_dp_train_step(cfg, opt_cfg, mesh, policy))
        state = {"params": params0, "opt": adamw_init(params0)}
        if policy.error_feedback:
            state["ef"] = ef_init(params0, group=8)
        losses = []
        for s in range(steps):
            state, m = step(state, data.batch(s))
            losses.append(float(m["loss"]))
        assert all(l == l and abs(l) < 1e6 for l in losses), losses
        print(
            f"[compressed-smoke] {label}: "
            f"loss {losses[0]:.3f} -> {losses[-1]:.3f} ({len(losses)} steps)"
        )
    print("[compressed-smoke] ok")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_example_lm")
    ap.add_argument("--compressed-smoke", action="store_true")
    args = ap.parse_args()

    if args.compressed_smoke:
        compressed_smoke(min(args.steps, 8))
        return

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    print(f"params ~= {LM_100M.param_count()/1e6:.1f}M")

    train_cfg = TrainConfig(
        steps=args.steps,
        seq_len=args.seq,
        global_batch=args.batch,
        checkpoint_every=50,
        optimizer=OptimizerConfig(
            lr=6e-4, schedule="cosine",
            warmup_steps=20, decay_steps=args.steps,
        ),
    )

    # phase 1: train to 60% of the run, then simulate a crash
    t0 = time.time()
    loop = build_training(LM_100M, train_cfg, ckpt_dir=args.ckpt_dir)
    crash_at = int(args.steps * 0.6)
    loop.run(crash_at)
    first = loop.metrics_log[0]["loss"]
    print(f"[phase 1] step {crash_at}: loss {loop.metrics_log[-1]['loss']:.3f}")
    del loop  # "crash": process state gone; checkpoints survive

    # phase 2: a fresh loop auto-resumes from the newest checkpoint
    loop = build_training(LM_100M, train_cfg, ckpt_dir=args.ckpt_dir)
    assert loop.start_step > 0, "must resume from checkpoint, not scratch"
    print(f"[phase 2] auto-resumed at step {loop.start_step}")
    loop.run(args.steps)
    last = loop.metrics_log[-1]["loss"]
    print(
        f"[done] steps={args.steps} loss {first:.3f} -> {last:.3f} "
        f"({time.time()-t0:.0f}s, stragglers={len(loop.monitor.events)})"
    )
    assert last < first - 0.5, "loss must drop materially"


if __name__ == "__main__":
    main()
