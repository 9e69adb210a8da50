"""Whole runs of the one-chip cells on the CPU at a small size, past the
harness's look for a chip: sound runs come out correct, and runs with
the timed path broken underneath do not.  Also: no result without a
TPU, and none from the benchmark's files alone."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from _bench_tiny import BENCH, ROOT, TINY, harness, module


def cell(name):
    return harness().load_cell(name, overrides=TINY[name])


def run_once(name, seed=2**31 + 99):
    return harness().run_cell(cell(name), seed, 1.0, False)


@pytest.mark.parametrize("name", ["minicpm-2b.train-1chip", "minicpm-2b.serve-backlog"])
def test_sound_run_is_correct(name):
    res = run_once(name)
    assert res["correct"], res["checks"]
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks" and res["compiles_in_window"] == 0


@pytest.mark.parametrize("name, fault", [
    ("minicpm-2b.train-1chip", "stale"),
    ("minicpm-2b.train-1chip", "half"),
    ("minicpm-2b.serve-backlog", "altered"),
    ("minicpm-2b.serve-backlog", "stale"),
])
def test_planted_fault_is_not_correct(name, fault):
    c = cell(name)
    driver = c.module("drivers", c.workload["driver"])
    with module("calibrate.py").planted(driver, fault):
        res = harness().run_cell(c, 5, 1.0, False)
    assert not res["correct"], res["checks"]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload", "minicpm-2b.train-1chip",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "allreduce-2x2.small",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_result_line_is_json_of_the_contract():
    res = run_once("minicpm-2b.train-1chip", seed=3)
    line = json.dumps(res)
    back = json.loads(line)
    for m in back["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(back["device"])
