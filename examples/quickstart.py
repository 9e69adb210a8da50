"""Quickstart: the paper's NAP allreduce in 30 lines.

Builds a virtual 4-pods x 4-chips mesh on CPU, runs the NAP allreduce
next to recursive doubling and SMP, and prints the inter-node
(collective-permute) step counts from the compiled HLO — the quantity
the paper minimises: log_ppn(n) vs log2(n).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import os

# virtual devices exist only on the CPU backend: never run on a chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import collectives
from repro.launch.mesh import make_mesh


def main():
    mesh = make_mesh((4, 4), ("pod", "data"))  # 4 "nodes" x 4 "ppn"
    x = jnp.arange(16.0).reshape(16, 1)  # one value per chip

    for algo in ["rd", "smp", "nap"]:
        fn = jax.jit(
            jax.shard_map(
                partial(
                    collectives.ALGORITHMS[algo],
                    inter_axes="pod",
                    intra_axes="data",
                ),
                mesh=mesh,
                in_specs=P(("pod", "data")),
                out_specs=P(("pod", "data")),
            )
        )
        result = np.unique(np.asarray(fn(x)))
        hlo = fn.lower(x).compile().as_text()
        permutes = hlo.count("collective-permute(")
        print(
            f"{algo:4s} allreduce -> {result} "
            f"(expected {float(np.asarray(x).sum())}), "
            f"inter-chip permute steps = {permutes}"
        )
    print("\nNAP: log_ppn(n) = log_4(4) = 1 step; RD: log2(16) = 4 steps.")


if __name__ == "__main__":
    main()
