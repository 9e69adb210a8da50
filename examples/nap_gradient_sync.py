"""The paper's technique inside training: NAP gradient synchronisation.

Trains the same small LM twice on a virtual 4-pods x 4-chips mesh — once
with XLA's stock psum gradient sync, once with the explicit NAP schedule
(paper §III) — and shows:

  1. losses match step for step (the schedule is numerically equivalent),
  2. the compiled HLO of the NAP step carries its inter-node traffic in
     log_ppn(n) collective-permutes per bucket (vs the baseline's opaque
     all-reduce),
  3. the simulated inter-pod cost of the scalar/bucket sync under the
     max-rate model (what the schedule would cost on a real 2-level
     fabric).

Run:  PYTHONPATH=src python examples/nap_gradient_sync.py
"""

import os

# virtual devices exist only on the CPU backend: never run on a chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import jax
import numpy as np

from repro.configs.base import ModelConfig, OptimizerConfig, SubLayer
from repro.core import perf_model as pm, simulator as sim
from repro.core.grad_sync import GradSyncConfig
from repro.data import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_dp_train_step
from repro.models import build_model
from repro.optim import adamw_init

CFG = ModelConfig(
    name="nap-demo-lm",
    family="dense",
    num_layers=4,
    d_model=128,
    num_heads=4,
    num_kv_heads=4,
    d_ff=512,
    vocab_size=1024,
    pattern=(SubLayer("attn"),),
    dtype="float32",
    remat="none",
)


def main():
    mesh = make_mesh((4, 4), ("pod", "data"))
    opt_cfg = OptimizerConfig(lr=1e-3, schedule="constant", warmup_steps=1)
    model = build_model(CFG)
    params0 = jax.jit(model.init)(jax.random.PRNGKey(0))
    data = SyntheticLM(
        vocab_size=CFG.vocab_size, seq_len=64, global_batch=16, seed=0,
        mesh=mesh, batch_axes=("pod", "data"),
    )

    losses = {}
    for algo in ["psum", "nap"]:
        step = jax.jit(
            make_dp_train_step(
                CFG, opt_cfg, mesh, GradSyncConfig(algorithm=algo)
            )
        )
        state = {"params": params0, "opt": adamw_init(params0)}
        ls = []
        for s in range(5):
            state, m = step(state, data.batch(s))
            ls.append(float(m["loss"]))
        losses[algo] = ls
        if algo == "nap":
            hlo = step.lower(state, data.batch(0)).compile().as_text()
            print(
                f"NAP train-step HLO: {hlo.count('collective-permute(')} "
                f"collective-permutes, {hlo.count('all-reduce(')} all-reduces"
            )
    print("psum losses:", [f"{l:.4f}" for l in losses["psum"]])
    print("nap  losses:", [f"{l:.4f}" for l in losses["nap"]])
    assert np.allclose(losses["psum"], losses["nap"], rtol=1e-4, atol=1e-5)
    print("=> numerically identical gradient sync\n")

    print("simulated scalar-sync cost on a 2048-node x 16-ppn fabric:")
    for algo in ["rd", "smp", "nap"]:
        t = sim.simulate_algorithm(algo, 2048, 16, 8.0, pm.BLUE_WATERS)
        print(f"  {algo:4s}: {t*1e6:7.2f} us")


if __name__ == "__main__":
    main()
