"""Small sizes at which the bench tests drive whole cells on the CPU,
and a loader for the harness's own files."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# widths at which random weights still give logits of a few tenths, so
# the float8 control stands as far above the program as at full size
MODEL = {"hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
         "num_key_value_heads": 4, "vocab_size": 2048}
TRAIN = {"config": MODEL, "workload": {"layers": 2}, "traffic": {"seq_len": 64, "rows_per_chip": 2}}
# the training driver on a (2, 2) mesh, as a four-chip cell would run it
TRAIN_4 = {**TRAIN, "workload": {"layers": 2, "mesh": {"shape": [2, 2], "axes": ["pod", "data"]}}}
SERVE = {
    "config": MODEL,
    "workload": {"layers": 2, "slots": 4, "max_len": 64, "buckets": [8, 16, 32], "sample_tokens": 40},
    "traffic": {"block": 4, "backlog_blocks": 40,
                "prompt": {"median": 10, "sigma": 1.0, "min": 2, "max": 32},
                "output": {"median": 8, "sigma": 0.8, "min": 2, "max": 24}, "max_total": 64},
}
ALLREDUCE = {"traffic": {"calls_per_launch": 16, "max_bytes": 1024}}
# the controls at the cell's own widths, two layers and a 4,096-token
# vocabulary: logits spread as at full size, so the float8 control reads
# on the scale the cell's limits were set on
WIDE = {"minicpm-2b.train-1chip": {**TRAIN, "config": {"vocab_size": 4096}},
        "minicpm-2b.serve-backlog": {**SERVE, "config": {"vocab_size": 4096}}}
TINY = {"minicpm-2b.train-1chip": TRAIN, "minicpm-2b.serve-backlog": SERVE, "allreduce-2x2.small": ALLREDUCE}


def harness():
    """``bench/run.py`` as a module."""
    if "bench_run" in sys.modules:
        return sys.modules["bench_run"]
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_run"] = mod
    spec.loader.exec_module(mod)
    return mod


def module(rel: str):
    return harness().load_module(BENCH / rel)
