import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # virtual devices: CPU backend only

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The three lines above MUST stay first (before any jax import) — jax locks
the device count at first backend init; the 512 virtual CPU devices make
``make_production_mesh()`` buildable on this single-CPU container.

For each cell this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds abstract state/batch (ShapeDtypeStruct only — no allocation),
  3. ``jax.jit(step).lower(...).compile()`` — proving the sharding config
     is coherent (no mismatched collectives, no OOM at compile),
  4. records memory_analysis / cost_analysis / HLO collective stats and
     the three roofline terms into reports/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  python -m repro.launch.dryrun --list
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from ..configs import ARCHS, SHAPES, get_config  # noqa: E402
from . import roofline as rl  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402
from .steps import (  # noqa: E402
    input_specs,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    microbatch_split,
    state_specs,
)

REPORTS = Path(__file__).resolve().parents[3] / "reports" / "dryrun"

# Applicability rules (DESIGN.md §4): long_500k only for sub-quadratic
# context growth (SSM / hybrid / windowed+alternating attention).
LONG_OK = {"gemma2-27b", "jamba-1.5-large-398b", "rwkv6-1.6b"}


def cells():
    for arch in sorted(ARCHS):
        for shape in SHAPES:
            if shape == "long_500k" and arch not in LONG_OK:
                continue
            yield arch, shape


def cell_name(arch: str, shape: str, multi_pod: bool) -> str:
    mesh = "pod2x16x16" if multi_pod else "pod16x16"
    return f"{arch}__{shape}__{mesh}"


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    seq_parallel: bool = False,
    opts: dict | None = None,
    tag: str = "",
    force: bool = False,
) -> dict:
    """opts (hillclimb levers; absent = paper/naive baseline):
    grad_fix=1       annotate grad-accum carry with param shardings
    remat=dots|none  scanned-stack remat policy override
    mamba_chunked=1  chunked mamba scan (checkpointed chunks)
    window_kv_slice=1  slice K/V to the sliding window per q chunk
    serve2d=1        serving layout: weights/cache over (model x data),
                     batch replicated — activation-sized collectives
    """
    opts = opts or {}
    name = cell_name(arch, shape_name, multi_pod) + (f"__{tag}" if tag else "")
    out_path = REPORTS / f"{name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    t0 = time.time()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(mesh.devices.size)
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "tag": tag,
        "opts": opts,
        "n_chips": n_chips,
        "ok": False,
    }
    try:
        import dataclasses as _dc

        cfg_overrides = {}
        for key in ("remat",):
            if key in opts:
                cfg_overrides[key] = opts[key]
        if "scan_unroll" in opts:
            cfg_overrides["scan_unroll"] = int(opts["scan_unroll"])
        for key in ("window_kv_slice", "bf16_bwd", "mamba_bf16_io"):
            if key in opts:
                cfg_overrides[key] = bool(int(opts[key]))
        model, policy, state, opt_cfg = state_specs(
            arch, shape_name, mesh,
            seq_parallel=seq_parallel,
            cfg_overrides=cfg_overrides,
            serve2d=bool(int(opts.get("serve2d", 0))),
        )
        cfg = model.cfg
        batch = input_specs(
            arch, shape_name, mesh,
            serve2d=bool(int(opts.get("serve2d", 0))),
        )

        if shape.kind == "train":
            n_micro = microbatch_split(cfg, shape, mesh)
            record["n_micro"] = n_micro
            grad_shardings = None
            if bool(int(opts.get("grad_fix", 0))):
                grad_shardings = jax.tree.map(
                    lambda s: s.sharding, state["params"]
                )
            step = make_train_step(
                model, opt_cfg, n_micro=n_micro,
                grad_shardings=grad_shardings,
            )
            args = ({"params": state["params"], "opt": state["opt"]}, batch)
        elif shape.kind == "prefill":
            step = make_prefill_step(model)
            args = (state["params"], batch)
        else:  # decode
            step = make_serve_step(model)
            tok = batch.get("tokens", batch.get("embeds"))
            args = (state["params"], state["cache"], tok)

        # donate the mutable state (train state / decode cache): real
        # deployments always do, and it lets XLA update caches in place
        # instead of copying the full KV buffer every step.
        donate = (0,) if shape.kind == "train" else (
            (1,) if shape.kind == "decode" else ()
        )
        with mesh:
            lowered = jax.jit(step, donate_argnums=donate).lower(*args)
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()

        record["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None
            ),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        }
        cost = dict(cost) if cost else {}
        record["cost"] = {
            k: float(v)
            for k, v in cost.items()
            if isinstance(v, (int, float)) and k in (
                "flops", "bytes accessed", "transcendentals",
                "bytes accessed output", "optimal_seconds",
            )
        }
        mf = rl.model_flops(cfg, shape)
        roof = rl.analyze(
            cost=cost, hlo_text=hlo, n_chips=n_chips, model_flops_total=mf
        )
        record["roofline"] = roof.to_dict()
        record["hlo_bytes"] = len(hlo)
        import gzip

        (REPORTS / f"{name}.hlo.gz").parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(REPORTS / f"{name}.hlo.gz", "wt") as fh:
            fh.write(hlo)
        record["ok"] = True
    except Exception as e:  # record failures — they are bugs to fix
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["compile_s"] = round(time.time() - t0, 2)

    REPORTS.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(record, indent=2, default=str))
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument(
        "--opts", default="",
        help="comma list key=val (grad_fix=1,remat=dots,mamba_chunked=1,"
        "window_kv_slice=1,serve2d=1)",
    )
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    opts = dict(
        kv.split("=", 1) for kv in args.opts.split(",") if "=" in kv
    )

    if args.list:
        for a, s in cells():
            print(f"{a} {s}")
        return

    if args.all:
        meshes = []
        if not args.multi_pod_only:
            meshes.append(False)
        if not args.single_pod_only:
            meshes.append(True)
        n_fail = 0
        for arch, shape in cells():
            for mp in meshes:
                rec = run_cell(
                    arch, shape, mp,
                    seq_parallel=args.seq_parallel,
                    opts=opts, tag=args.tag, force=args.force,
                )
                status = "OK " if rec["ok"] else "FAIL"
                n_fail += 0 if rec["ok"] else 1
                dom = rec.get("roofline", {}).get("dominant", "-")
                print(
                    f"{status} {cell_name(arch, shape, mp):56s} "
                    f"compile={rec.get('compile_s', 0):7.1f}s dominant={dom}",
                    flush=True,
                )
        print(f"failures: {n_fail}")
        raise SystemExit(1 if n_fail else 0)

    rec = run_cell(
        args.arch, args.shape, args.multi_pod,
        seq_parallel=args.seq_parallel, opts=opts, tag=args.tag,
        force=args.force,
    )
    print(json.dumps(rec, indent=2, default=str))
    raise SystemExit(0 if rec["ok"] else 1)


if __name__ == "__main__":
    main()
