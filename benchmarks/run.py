"""Benchmark runner: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (``--quick``:
``name,platform,device_kind,cpu_us_per_call,derived``).  Sections:
  * paper_figures   — Figs 10-18 + §III message-count tables
  * gradsync        — gradient-sync schedule comparison (training buckets)
  * roofline_report — per-(arch x shape) roofline terms, if dry-run
                      artifacts exist under reports/dryrun/

``--quick`` runs a CPU smoke instead: one NAP shape (latency regime),
one MLA shape (bandwidth regime) and one chunk-pipelined MLA shape
(ragged payload, C=2) are *executed* end to end on a virtual 2x4 device
mesh, checked against the NumPy oracle and timed — so perf or
correctness regressions on the hot path are catchable without hardware.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
for p in (str(_ROOT), str(_ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def quick_smoke() -> int:
    """Execute one NAP + one MLA allreduce on a virtual CPU mesh."""
    # virtual devices exist only on the CPU backend: never time a chip here
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    )
    import time
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import collectives
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("pod", "data"))
    rng = np.random.default_rng(0)
    failures = 0
    # every row names the device it ran on; the time is XLA:CPU's
    dev = jax.devices()[0]
    where = f"{dev.platform},{dev.device_kind}"
    print("name,platform,device_kind,cpu_us_per_call,derived")
    cases = [
        ("nap", 8, {}),
        ("mla", 1 << 16, {}),
        # ragged payload through the chunked lowering
        ("mla_pipelined", (1 << 16) + 37, {"pipeline_chunks": 2}),
    ]
    for algo, size, kw in cases:
        xs = jnp.asarray(rng.normal(size=(8, size)).astype(np.float32))
        fn = jax.jit(
            jax.shard_map(
                partial(
                    collectives.ALGORITHMS[algo],
                    inter_axes="pod",
                    intra_axes="data",
                    **kw,
                ),
                mesh=mesh,
                in_specs=P(("pod", "data")),
                out_specs=P(("pod", "data")),
            )
        )
        got = np.asarray(fn(xs))  # compile + correctness
        want = np.asarray(xs).sum(axis=0)
        ok = np.allclose(got, np.tile(want, (8, 1)), rtol=1e-4, atol=1e-4)
        failures += 0 if ok else 1
        iters = 50
        jax.block_until_ready(fn(xs))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(xs)
        jax.block_until_ready(out)
        us = (time.perf_counter() - t0) / iters * 1e6
        print(
            f"quick_{algo}_s{size * 4},{where},{us:.3f},"
            f"{'ok' if ok else 'MISMATCH'}"
        )

    # compressed transport smoke: the fused quantize-pack engine end to
    # end (interpret-mode Pallas kernels on CPU), int8 and packed int4
    from repro.core import comm

    size = 1 << 15
    xs = jnp.asarray(rng.normal(size=(8, size)).astype(np.float32))
    want = np.asarray(xs).mean(axis=0)
    qtol = float(np.abs(np.asarray(xs)).max())
    for bits in (8, 4):
        policy = comm.CommPolicy(
            algorithm="nap", mean=True, compress_bits=bits
        )

        def f(x):
            topo = comm.Topology.from_mesh(mesh)
            ctx = comm.CommContext(topo, policy)
            return ctx.sync_grads({"w": x})["w"]

        fn = jax.jit(
            jax.shard_map(
                f, mesh=mesh,
                in_specs=P(("pod", "data")),
                out_specs=P(("pod", "data")),
                check_vma=False,
            )
        )
        got = np.asarray(fn(xs))
        # mean-of-sum error bound: group*A/qmax on the sum -> A/qmax here
        atol = qtol / float(2 ** (bits - 1) - 1) * 1.01 + 1e-6
        ok = bool(np.all(np.abs(got - np.tile(want, (8, 1))) <= atol))
        failures += 0 if ok else 1
        iters = 20
        jax.block_until_ready(fn(xs))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(xs)
        jax.block_until_ready(out)
        us = (time.perf_counter() - t0) / iters * 1e6
        print(
            f"quick_compressed_int{bits}_s{size * 4},{where},{us:.3f},"
            f"{'ok' if ok else 'MISMATCH'}"
        )

    # serving spine smoke: continuous batching through the meshed
    # tensor-parallel decode path (repro.serve), staggered arrivals
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serve import PromptBuckets, ServeEngine

    cfg = reduced(get_config("minicpm-2b"))
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServeEngine(
        model, params, num_slots=8, max_len=24,
        buckets=PromptBuckets([8]), mesh=mesh,
    )
    disp = engine.dispatch_report()
    reqs = [engine.submit([1, 2, 3], 6), engine.submit([4, 5], 4)]
    engine.step()
    reqs.append(engine.submit([6, 7, 8, 9], 5))  # joins in flight
    t0 = time.perf_counter()
    out = engine.run()
    dt = time.perf_counter() - t0
    ok = (
        all(len(out[r.rid]) == r.max_new_tokens for r in reqs)
        and engine.idle
        and disp["logits_allreduce"]["engine"] == "nap"
    )
    failures += 0 if ok else 1
    us = dt / max(engine.n_decode_steps, 1) * 1e6
    print(
        f"quick_serve_engine_{disp['logits_allreduce']['engine']},{where},"
        f"{us:.3f},{'ok' if ok else 'MISMATCH'}"
    )
    return failures


def main() -> None:
    if "--quick" in sys.argv[1:]:
        sys.exit(quick_smoke())

    print("name,us_per_call,derived")
    from benchmarks import paper_figures

    for fn in paper_figures.ALL:
        fn()

    from benchmarks import gradsync

    gradsync.main()

    from benchmarks import roofline_report

    roofline_report.main()


if __name__ == "__main__":
    main()
