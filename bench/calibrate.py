#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1-12 [--control 3]
        [--faults half,stale] [--seconds 5]

For each seed: a run of the program at the cell's own size with a short
window (the training cells' readings need none), and the numbers its
check compares.  ``--control N``: the control (the plain reference in the
program's place, one precision lower) on the first N seeds.
``--faults``: the program with each named fault of the driver planted,
on the first three seeds.  One JSON line per reading on stdout, and in
``<--out>/calibrate_<cell>.jsonl``.  Needs the cell's chips; never part
of a benchmark run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


@contextlib.contextmanager
def planted(driver, name: str):
    """Plant one of ``driver.FAULTS`` in the program for the block."""
    owner, attr, wrap = driver.FAULTS[name]()
    orig = getattr(owner, attr)
    setattr(owner, attr, wrap(orig))
    try:
        yield
    finally:
        setattr(owner, attr, orig)


def program_readings(cell, seed: int, seconds: float, log) -> dict:
    driver = cell.module("drivers", cell.workload["driver"])
    spans = run.Spans()
    s = driver.setup(cell, seed, spans, log)
    measured = driver.window(s, run.Window(seconds, False, seconds, spans))
    return {c["name"]: c["value"] for c in driver.check(s, measured, log)}


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b) + 1) if b else [int(a)])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Readings for a cell's limits.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--program", type=int, default=1, help="0: skip the program's seeds")
    ap.add_argument("--out", default=str(run.ROOT / ".bench_out"), help="directory for the readings")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    import jax

    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = run.device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"needs {cell.chips} TPU chips; JAX found {dev}", file=sys.stderr)
        return 2
    driver = cell.module("drivers", cell.workload["driver"])
    out = Path(args.out) / f"calibrate_{cell.name}.jsonl"
    out.parent.mkdir(exist_ok=True)
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731

    def emit(kind, seed, values, t0):
        row = {"cell": cell.name, "kind": kind, "seed": seed, "seconds": time.perf_counter() - t0, **values}
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")

    ss = seeds(args.seeds)
    for seed in ss if args.program else []:
        t0 = time.perf_counter()
        emit("program", seed, program_readings(cell, seed, args.seconds, log), t0)
        jax.clear_caches()
    for seed in ss[: args.control]:
        t0 = time.perf_counter()
        rows = driver.control(cell, seed, log)
        emit("control", seed, {**{r["name"]: r["value"] for r in rows}, "correct": run.verdict(rows)}, t0)
        jax.clear_caches()
    for name in filter(None, args.faults.split(",")):
        for seed in ss[:3]:
            t0 = time.perf_counter()
            with planted(driver, name):
                emit(f"fault:{name}", seed, program_readings(cell, seed, args.seconds, log), t0)
            jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
