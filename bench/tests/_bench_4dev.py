"""Runs the four-chip cells at a small size on four virtual CPU devices
and prints one JSON object of outcomes (run by test_bench_cells_4dev.py
in a process of its own, since the device count is fixed at start)."""

import json
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _bench_tiny import TINY, TRAIN_4, harness, module  # noqa: E402


def main():
    run, calibrate = harness(), module("calibrate.py")
    out = {}
    for key, name, over, faults in (
            ("allreduce-2x2.small", "allreduce-2x2.small", TINY["allreduce-2x2.small"], ("no_exchange", "altered")),
            ("train-2x2", "minicpm-2b.train-1chip", TRAIN_4, ("no_exchange", "half"))):
        cell = run.load_cell(name, overrides=over)
        cell.chips = 4
        name = key
        driver = cell.module("drivers", cell.workload["driver"])
        res = run.run_cell(cell, 2**31 + 3, 1.0, False)
        out[name] = {"correct": res["correct"], "checks": res["checks"],
                     "count": res["device"]["count"], "metrics": sorted(res["metrics"])}
        for f in faults:
            with calibrate.planted(driver, f):
                out[f"{name}:{f}"] = run.run_cell(cell, 17, 1.0, False)["correct"]
        if name == "allreduce-2x2.small":
            out[f"{name}:control"] = run.verdict(driver.control(cell, 11, lambda m: None))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
