"""Grid-sweep verification driver: ``python -m repro.analysis``.

Runs both static-analysis passes and emits the ``BENCH_7.json``
verification table:

1. **Schedule sweep** — every registered engine x the grid matrix
   (degenerate ``n=1``/``ppn=1`` grids, prime node counts, ragged
   payloads, chunk depths) through the four schedule-verifier passes.
   Engines without a schedule builder (the native psum fallbacks) are
   reported as ``native`` rows — a single native collective has no
   message schedule to verify.
2. **HLO wire-lint** — compiles the compressed fused-bucket gradient
   sync on 8 virtual CPU devices and runs the wire-dtype,
   collective-count and stable-lowering rules over the jaxpr and the
   optimized HLO.

3. **SPMD jaxpr lint** (``--spmd``, the ``BENCH_8.json`` gate) — the
   middle layer of the proof chain: every registered engine's *executed*
   lowering is traced to a jaxpr and checked for collective uniformity,
   axis discipline, numerics flow and schedule-vs-jaxpr byte equality
   (:func:`repro.core.comm.lint_lowering`), then the same rules run
   over the compressed grad-sync step, the data-parallel train step and
   the serve decode loop.

4. **Protocol model check** (``--protocol``, the ``BENCH_10.json``
   gate) — layer 0 of the proof chain: exhaustive explicit-state
   exploration of the serving control plane (scheduler + router +
   replica health, the real objects) over the small-scope grid in
   :data:`PROTOCOL_GRID`; fails on any safety/liveness violation *or*
   on state-space coverage regressing below the recorded floor, and
   re-asserts the layer-0 ↔ layer-2 link (admissible slot occupancies
   == the ragged geometry the decode slice is linted at).

Exits non-zero on any violation, so CI can gate on it::

    PYTHONPATH=src python -m repro.analysis --json reports/BENCH_7.json
    PYTHONPATH=src python -m repro.analysis --spmd --json reports/BENCH_8.json
    PYTHONPATH=src python -m repro.analysis --protocol --json reports/BENCH_10.json

``--skip-hlo`` runs only the (fast, jax-free) schedule sweep;
``--skip-schedules`` only the lint.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# the HLO pass compiles on virtual CPU devices: the flag must be set
# before anything imports jax, which is why this module (and the whole
# analysis package) keeps jax out of module scope
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"  # virtual devices: CPU backend only


def run_schedule_sweep() -> dict:
    from repro.core import comm

    from . import schedule_verifier as sv

    rows = []
    per_engine: dict[str, dict] = {}
    for key in sorted(comm.registered_engines()):
        collective, name = key.split(":", 1)
        spec = comm.get_engine(name, collective)
        reports = sv.verify_spec_grid(spec)
        n_bad = sum(1 for r in reports if not r.ok)
        n_native = sum(
            1 for r in reports if any(n.startswith("native") for n in r.notes)
        )
        n_skipped = sum(
            1 for r in reports if any(n.startswith("skipped") for n in r.notes)
        )
        per_engine[key] = {
            "cells": len(reports),
            "verified": len(reports) - n_bad - n_native - n_skipped,
            "native": n_native,
            "skipped_below_min_grid": n_skipped,
            "violations": n_bad,
        }
        rows.extend(r.to_row() for r in reports)
        status = "FAIL" if n_bad else "ok"
        print(
            f"  {key:28s} {per_engine[key]['verified']:4d} verified "
            f"{n_native:4d} native {n_skipped:4d} skipped "
            f"{n_bad:3d} violations  {status}"
        )
    n_violations = sum(e["violations"] for e in per_engine.values())
    return {
        "grid_matrix": [list(g) for g in sv.GRID_MATRIX],
        "payload_elems": list(sv.PAYLOAD_ELEMS),
        "engines": per_engine,
        "cells": len(rows),
        "violations": n_violations,
        "rows": rows,
    }


def run_hlo_lint() -> dict:
    """Compile the compressed fused-bucket grad sync and lint its wire."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import comm, grad_sync
    from repro.launch.mesh import make_mesh

    from . import hlo_lint

    mesh = make_mesh((2, 4), ("pod", "data"))
    shapes = [(64 + 32 * i,) for i in range(3)]
    payload_elems = sum(s[0] for s in shapes)

    def compiled(bits):
        policy = comm.CommPolicy(
            algorithm="nap", mean=True, compress_bits=bits
        )

        def f(*leaves):
            topo = comm.Topology.from_mesh(mesh)
            ctx = comm.CommContext(topo, policy)
            plan = grad_sync.plan_for_tree(
                [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes],
                cfg=policy, topology=topo,
            )
            out = grad_sync.sync_with_context(list(leaves), ctx, plan=plan)
            return jnp.concatenate(out)

        args = [jnp.zeros(s, jnp.float32) for s in shapes]
        g = jax.shard_map(
            f, mesh=mesh,
            in_specs=tuple(P() for _ in args),
            out_specs=P(),
            check_vma=False,
        )
        return g, args

    rows = []

    def record(context: str, violations) -> None:
        for v in violations:
            rows.append({"context": context, **v.to_row()})
        status = "FAIL" if violations else "ok"
        print(f"  {context:42s} {len(violations):2d} violations  {status}")

    for bits in (8, 4):
        g, args = compiled(bits)
        jaxpr = str(jax.make_jaxpr(g)(*args))
        record(
            f"jaxpr[bits={bits}] pallas_call budget",
            hlo_lint.lint_collective_counts(jaxpr, {"pallas_call": 4}),
        )
        hlo = jax.jit(g).lower(*args).compile().as_text()
        record(
            f"hlo[bits={bits}] compressed wire",
            hlo_lint.lint_compressed_wire(
                hlo, bits=bits, payload_elems=payload_elems, ppn=4
            ),
        )
        record(
            f"hlo[bits={bits}] replica-group partition",
            hlo_lint.lint_replica_groups(
                hlo, num_devices=len(mesh.devices.flat)
            ),
        )
    g, args = compiled(8)
    record(
        "stable lowering (no silent recompile)",
        hlo_lint.lint_stable_lowering(g, *args),
    )
    return {"rows": rows, "violations": len(rows)}


#: engine-cell matrix of the --spmd sweep: grids past the engines'
#: minimums plus one asymmetric shape, at full and half wire precision
_SPMD_GRIDS = ((2, 2), (3, 2), (2, 4))
_SPMD_DTYPES = ("float32", "bfloat16")


def run_spmd_sweep() -> dict:
    """Trace-and-lint sweep over every engine lowering + the compiled
    workloads (grad sync, train step, serve decode)."""
    import jax
    import jax.numpy as jnp

    from repro.core import comm, grad_sync

    from . import spmd_lint

    rows = []
    n_violations = 0

    def record(rep) -> None:
        nonlocal n_violations
        rows.append(rep.to_row())
        n_violations += len(rep.violations)
        status = "FAIL" if rep.violations else "ok"
        byte_col = (
            "bytes=?"
            if rep.internode_bytes_per_chip is None
            else f"bytes={rep.internode_bytes_per_chip:g}"
            + ("" if rep.declared_bytes is None else "=declared")
        )
        print(
            f"  {rep.label:40s} {rep.collectives:3d} collectives "
            f"{byte_col:18s} {len(rep.violations):2d} violations  {status}"
        )

    # -- 1. every registered engine's executed lowering ------------------
    per_engine: dict[str, dict] = {}
    byte_verified = 0
    for key in sorted(comm.registered_engines()):
        collective, name = key.split(":", 1)
        spec = comm.get_engine(name, collective)
        cells = skipped = bounded = bad = 0
        for n, p in _SPMD_GRIDS:
            if n < spec.min_nodes or p < spec.min_ppn:
                skipped += len(_SPMD_DTYPES)
                continue
            for dt in _SPMD_DTYPES:
                rep = comm.lint_lowering(
                    name, n_nodes=n, ppn=p, dtype=dt,
                    raise_on_violation=False,
                )
                record(rep)
                cells += 1
                if rep.declared_bytes is not None:
                    bounded += 1
                    byte_verified += 1
                if not rep.ok:
                    bad += 1
        per_engine[key] = {
            "cells": cells,
            "byte_verified": bounded,
            "skipped_below_min_grid": skipped,
            "violations": bad,
        }

    # -- 2. the compressed grad-sync step (shard-level trace) ------------
    topo = comm.Topology(2, 4, inter_axes=("pod",), intra_axes=("data",))
    axis_env = [("pod", 2), ("data", 4)]
    axis_sizes = dict(axis_env)
    shapes = [(64 + 32 * i,) for i in range(3)]
    leaves = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    for bits in (8, 4):
        policy = comm.CommPolicy(
            algorithm="nap", mean=True, compress_bits=bits
        )
        ctx = comm.CommContext(topo, policy)
        plan = grad_sync.plan_for_tree(leaves, cfg=policy, topology=topo)

        def f(*ls):
            return jnp.concatenate(
                grad_sync.sync_with_context(list(ls), ctx, plan=plan)
            )

        closed = jax.make_jaxpr(f, axis_env=axis_env)(*leaves)
        record(
            spmd_lint.lint_jaxpr(
                closed, axis_sizes=axis_sizes,
                inter_axes=("pod",), intra_axes=("data",),
                label=f"grad_sync[bits={bits}]",
            )
        )

    # -- 3. the data-parallel train step (launch/steps) ------------------
    import dataclasses as _dc

    from repro.configs import ARCHS, reduced
    from repro.configs.base import OptimizerConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_dp_train_step
    from repro.models import build_model
    from repro.optim import adamw_init

    mesh = make_mesh((2, 4), ("pod", "data"))
    cfg = _dc.replace(reduced(ARCHS["minicpm-2b"]), dtype="float32")
    opt_cfg = OptimizerConfig(lr=1e-2, schedule="constant", warmup_steps=1)
    model = build_model(cfg)
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state_sds = {
        "params": params_sds,
        "opt": jax.eval_shape(adamw_init, params_sds),
    }
    batch_sds = {"tokens": jax.ShapeDtypeStruct((16, 32), jnp.int32)}
    step = make_dp_train_step(
        cfg, opt_cfg, mesh, grad_sync.GradSyncConfig(
            algorithm="nap", mean=True,
        ),
    )
    closed = jax.make_jaxpr(step)(state_sds, batch_sds)
    record(
        spmd_lint.lint_jaxpr(
            closed, axis_sizes=axis_sizes,
            inter_axes=("pod",), intra_axes=("data",),
            label="train_step[nap]",
            # mesh-level program: the step's own shard_map binds the
            # axes; inputs are host values, uniform until sharded
            axes_bound_at_root=False,
        )
    )

    # -- 4. the serve decode loop (launch/serve) -------------------------
    from repro.launch import serve as serve_mod

    serve_model = build_model(cfg)
    shard_fn = serve_mod.make_serve_shard(
        serve_model, comm.CommContext(topo), gen_len=4, max_len=10,
        eos_id=1,
    )
    prompts_sds = jax.ShapeDtypeStruct((1, 6), jnp.int32)
    closed = jax.make_jaxpr(shard_fn, axis_env=axis_env)(
        params_sds, prompts_sds
    )
    record(
        spmd_lint.lint_jaxpr(
            closed, axis_sizes=axis_sizes,
            inter_axes=("pod",), intra_axes=("data",),
            label="serve_decode[eos early-exit]",
        )
    )

    # -- 5. the serving spine's scheduler-driven decode slice ------------
    # (repro.serve): slot-stacked continuous-batching decode with the
    # tensor-parallel logits head — allgather + latency-regime allreduce
    # + psum-min early exit, the full decode-collective set per token
    import functools

    from repro.serve import decode as serve_decode

    from .protocol_check import verify_decode_geometry_link

    # layer-0 <-> layer-2 link: the batch width this slice is linted at
    # comes from the protocol checker's admissible-occupancy closure
    # over a real Scheduler — the linted shape IS the proved geometry
    link = verify_decode_geometry_link(8, topo.group)
    b_max = link["b_max"]  # max of the ragged_splits slot geometry
    b1_cache_sds = jax.eval_shape(
        functools.partial(serve_model.init_decode, batch_size=1, max_len=10),
        params_sds,
    )
    cache_sds = jax.eval_shape(
        functools.partial(serve_decode.stack_slot_cache, slots=b_max),
        b1_cache_sds,
    )
    tok_sds = jax.ShapeDtypeStruct((b_max, 1), jnp.int32)
    active_sds = jax.ShapeDtypeStruct((b_max,), jnp.bool_)
    slice_fn = serve_decode.make_decode_slice(
        serve_model, comm.CommContext(topo), slice_len=4, eos_id=1
    )
    closed = jax.make_jaxpr(slice_fn, axis_env=axis_env)(
        params_sds, cache_sds, tok_sds, active_sds
    )
    record(
        spmd_lint.lint_jaxpr(
            closed, axis_sizes=axis_sizes,
            inter_axes=("pod",), intra_axes=("data",),
            label="serve_engine[continuous batching]",
        )
    )

    return {
        "grids": [list(g) for g in _SPMD_GRIDS],
        "dtypes": list(_SPMD_DTYPES),
        "engines": per_engine,
        "byte_verified_cells": byte_verified,
        "cells": len(rows),
        "violations": n_violations,
        "rows": rows,
    }


#: the --protocol small-scope grid: (config, recorded state floor).
#: Exploration is deterministic, so the floors are the exact counts at
#: the time of recording; CI fails if coverage ever regresses below
#: them (a canonicalization or event-alphabet change silently shrinking
#: the explored space would otherwise look like a pass).
def _protocol_grid():
    from .protocol_check import CheckConfig

    return (
        # pure scheduler protocol, single replica, full closure
        (CheckConfig(replicas=1, slots=2, queue=2, requests=4,
                     budgets=(2, 1), faults=False, losses=False,
                     depth=None), 230),
        # two replicas with the full fault alphabet, full closure
        (CheckConfig(replicas=2, slots=1, queue=1, requests=3,
                     budgets=(2, 1), recovery=2, depth=None), 3591),
        # three replicas: reroute fan-out + double loss, bounded depth
        (CheckConfig(replicas=3, slots=1, queue=1, requests=4,
                     budgets=(1,), recovery=2, depth=8), 9890),
        # the acceptance scope: 2 replicas x 3 slots x 5 requests to
        # event depth 12 (the ISSUE-10 floor), full fault alphabet
        (CheckConfig(replicas=2, slots=3, queue=2, requests=5,
                     budgets=(2, 1), recovery=2, depth=12), 77796),
    )


def run_protocol_sweep() -> dict:
    """Exhaustive layer-0 sweep over the small-scope protocol grid."""
    from . import protocol_check as pc

    rows = []
    n_violations = 0
    coverage_failures = 0
    for cfg, floor in _protocol_grid():
        rep = pc.check_protocol(cfg)
        row = rep.to_row()
        row["min_states"] = floor
        row["coverage_ok"] = rep.states >= floor
        rows.append(row)
        n_violations += len(rep.violations)
        if not row["coverage_ok"]:
            coverage_failures += 1
        status = (
            "FAIL"
            if rep.violations or not row["coverage_ok"]
            else "ok"
        )
        scope = (
            f"r{cfg.replicas} s{cfg.slots} q{cfg.queue} "
            f"n{cfg.requests} d{cfg.depth or 'closure'}"
        )
        print(
            f"  {scope:28s} {rep.states:7d} states "
            f"(floor {floor:7d}) {rep.transitions:8d} transitions "
            f"dedup {rep.dedup_ratio:5.2f}x depth {rep.depth:2d} "
            f"{len(rep.violations):2d} violations  {status}"
        )
        for v in rep.violations:
            print(f"    !! [{v.rule}] {v.detail}")
            print(f"       trace: {list(v.trace)}")

    # layer-0 <-> layer-2 link: the occupancies the protocol admits are
    # exactly the ragged slot geometry the --spmd sweep lints the
    # decode slice at (same Scheduler.shard_geometry call, both sides)
    from repro.core import comm

    topo = comm.Topology(2, 4, inter_axes=("pod",), intra_axes=("data",))
    link = pc.verify_decode_geometry_link(8, topo.group)
    print(
        f"  layer-2 link: occupancies 0..{max(link['admissible_occupancies'])}"
        f" on geometry {link['geometry']} -> b_max={link['b_max']}  ok"
    )
    return {
        "rows": rows,
        "layer2_link": link,
        "coverage_failures": coverage_failures,
        "states_total": sum(r["states"] for r in rows),
        "transitions_total": sum(r["transitions"] for r in rows),
        # the CI gate: protocol violations AND coverage regressions
        # both fail the run
        "violations": n_violations + coverage_failures,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis", description=__doc__
    )
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the verification table here "
                         "(BENCH_7, or BENCH_8 with --spmd)")
    ap.add_argument("--spmd", action="store_true",
                    help="run the SPMD jaxpr lint sweep (BENCH_8) "
                         "instead of the BENCH_7 passes")
    ap.add_argument("--protocol", action="store_true",
                    help="run the layer-0 protocol model check over "
                         "the small-scope grid (BENCH_10)")
    ap.add_argument("--skip-hlo", action="store_true",
                    help="schedule sweep only (fast, jax-free)")
    ap.add_argument("--skip-schedules", action="store_true",
                    help="HLO lint only")
    args = ap.parse_args(argv)

    if args.protocol:
        report = {"bench": "BENCH_10", "ok": True}
        print("protocol model check (layer 0):")
        report["protocol"] = run_protocol_sweep()
    elif args.spmd:
        report = {"bench": "BENCH_8", "ok": True}
        print("SPMD jaxpr lint sweep:")
        report["spmd_lint"] = run_spmd_sweep()
    else:
        report = {"bench": "BENCH_7", "ok": True}
        if not args.skip_schedules:
            print("schedule verification sweep:")
            report["schedule_verification"] = run_schedule_sweep()
        if not args.skip_hlo:
            print("HLO wire lint:")
            report["hlo_lint"] = run_hlo_lint()

    n_violations = sum(
        report.get(k, {}).get("violations", 0)
        for k in (
            "schedule_verification", "hlo_lint", "spmd_lint", "protocol",
        )
    )
    report["ok"] = n_violations == 0

    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.json}")

    if not report["ok"]:
        print(f"FAILED: {n_violations} violation(s)")
        return 1
    print("all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
