"""The train_mfu FLOP count and the peaks table beside it."""

from __future__ import annotations

import json

import pytest

from _bench_tiny import BENCH, harness, module

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 4, "vocab_size": 512}


def test_flops_at_a_reduced_config():
    f = module("flops.py")
    # per layer: q, k, v, o 4 * 64 * 64 + gate, up, down 3 * 64 * 128; head 512 * 64
    n = 2 * (4 * 64 * 64 + 3 * 64 * 128) + 512 * 64
    assert f.matmul_params(TINY, 2) == n
    assert f.train_flops_per_token(TINY, 2, 32) == 6 * n + 6 * 2 * 64 * 32


def test_flops_count_kv_heads():
    f = module("flops.py")
    gqa = dict(TINY, num_key_value_heads=1)
    assert f.matmul_params(TINY, 1) - f.matmul_params(gqa, 1) == 2 * 64 * (64 - 16)


def test_minicpm_train_cell_flops():
    f = module("flops.py")
    cfg = json.loads((BENCH / "configs" / "minicpm-2b.json").read_text())
    n = f.matmul_params(cfg, 8)
    assert n == pytest.approx(771.1e6, rel=1e-3)
    assert f.train_flops_per_token(cfg, 8, 512) == pytest.approx(4.68e9, rel=1e-2)


def test_peaks_table_is_keyed_by_device_kind():
    table = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_an_error():
    run = harness()
    ctx = run.MetricContext(cell=None, trace=None, traced={}, measured={}, trace_mod=None, bench=BENCH)
    with pytest.raises(KeyError):
        ctx.peaks()  # the CPU backend has no peaks
