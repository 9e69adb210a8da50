"""Scope names in compiled multi-device programs, run by test_tracing.py.

Must be executed as a script: sets XLA_FLAGS before importing jax,
compiles the collectives and the data-parallel train step on a (2, 2)
mesh of virtual CPU devices and prints, per program, the opcode and
``op_name`` of every instruction that has one, as one JSON blob.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", "")
)

import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import comm  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

_INSTR = re.compile(r" = .*?\s([a-z][a-z0-9-]*)\(.*metadata=\{op_name=\"([^\"]*)\"")


def op_names(lowered) -> list[list[str]]:
    """``[opcode, op_name]`` of each instruction that has an op_name."""
    text = lowered.compile().as_text()
    return [list(m.groups()) for m in map(_INSTR.search, text.splitlines()) if m]


def collectives(mesh):
    ctx = comm.CommContext(comm.Topology.from_mesh(mesh))
    spec = P(("pod", "data"))
    x = jnp.arange(4 * 64, dtype=jnp.float32)
    calls = {
        "nap": lambda v: ctx.allreduce(v, algorithm="nap"),
        "mla": lambda v: ctx.allreduce(v, algorithm="mla"),
        "reduce_scatter": lambda v: ctx.reduce_scatter(v),
        "allgather": lambda v: ctx.allgather(v),
    }
    out = {}
    for name, f in calls.items():
        fn = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
        out[name] = op_names(fn.lower(x))
    return out


def train_step(mesh):
    from repro.configs import ARCHS, reduced
    from repro.configs.base import OptimizerConfig
    from repro.core.grad_sync import GradSyncConfig
    from repro.data import SyntheticLM
    from repro.launch.steps import make_dp_train_step
    from repro.models import build_model
    from repro.optim import adamw_init

    cfg = dataclasses.replace(reduced(ARCHS["minicpm-2b"]), dtype="float32")
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    state = {"params": params, "opt": jax.eval_shape(adamw_init, params)}
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4, seed=3,
                       mesh=mesh, batch_axes=("pod", "data"))
    step = jax.jit(make_dp_train_step(
        cfg, OptimizerConfig(lr=1e-3, schedule="constant", warmup_steps=1), mesh,
        GradSyncConfig(algorithm="nap", mean=True)))
    return op_names(step.lower(state, data.batch(0)))


def main():
    mesh = make_mesh((2, 2), ("pod", "data"))
    out = collectives(mesh)
    out["train_step"] = train_step(mesh)
    print("RESULTS_JSON:" + json.dumps(out))


if __name__ == "__main__":
    main()
