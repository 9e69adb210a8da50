"""The four-chip paths at a small size on four virtual CPU devices: the
allreduce cell, and the training driver on a (2, 2) mesh as a four-chip
training cell would run it.  Sound runs are correct; with the exchange
between chips left out, an answer altered, half the batch left out, or
(for the allreduce cell) the control in the program's place, they are
not."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from _bench_tiny import BENCH, ROOT


@pytest.fixture(scope="module")
def outcomes():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "tests" / "_bench_4dev.py")], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", ["allreduce-2x2.small", "train-2x2"])
def test_sound_run_is_correct(outcomes, name):
    o = outcomes[name]
    assert o["correct"], o["checks"]
    assert o["count"] == 4 and "setup_s" in o["metrics"]


@pytest.mark.parametrize("key", [
    "allreduce-2x2.small:no_exchange", "allreduce-2x2.small:altered",
    "train-2x2:no_exchange", "train-2x2:half",
])
def test_planted_fault_is_not_correct(outcomes, key):
    assert outcomes[key] is False


def test_control_fails_a_limit(outcomes):
    assert outcomes["allreduce-2x2.small:control"] is False
