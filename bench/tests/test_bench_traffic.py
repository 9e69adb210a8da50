"""The seeded traffic generator: the same seed gives the same inputs,
another seed other inputs over the same multiset of sizes."""

from __future__ import annotations

import itertools
import json

import numpy as np
import pytest

from _bench_tiny import BENCH, module

BIG = 2**31 + 12345


@pytest.fixture(scope="module")
def gen():
    return module("traffic/gen.py")


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_payload_schedule(gen, seed):
    m = mix("allreduce-small")
    a, b, c = (list(itertools.islice(gen.payload_schedule(m, s), 400)) for s in (seed, seed, seed + 1))
    assert a == b and a != c
    sizes = gen.payload_sizes(m)
    assert sizes == [4, 16, 64, 256, 1024, 4096, 16384, 65536]
    for k in range(50):  # every block holds every size once
        assert sorted(a[8 * k: 8 * k + 8]) == sizes


def test_payload_inputs(gen):
    m = mix("allreduce-small")
    x, d = gen.payload_inputs(m, BIG, 64, 4)
    x2, d2 = gen.payload_inputs(m, BIG, 64, 4)
    assert x.shape == d.shape == (4, 16) and x.dtype == np.float32
    assert np.array_equal(x, x2) and np.array_equal(d, d2)
    assert not np.array_equal(x, gen.payload_inputs(m, BIG + 1, 64, 4)[0])
    assert np.abs(d.astype(np.float64).sum(axis=0)).max() < 1e-5


@pytest.mark.parametrize("seed", [3, BIG])
def test_lm_batches(gen, seed):
    m = mix("lm-4x512")
    a, b = gen.lm_batch(m, seed, 5, 4, 122753), gen.lm_batch(m, seed, 5, 4, 122753)
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert a["tokens"].shape == (4, 512) and a["tokens"].max() < 122753
    assert not np.array_equal(a["tokens"], gen.lm_batch(m, seed, 6, 4, 122753)["tokens"])
    assert not np.array_equal(a["tokens"], gen.lm_batch(m, seed + 1, 5, 4, 122753)["tokens"])
    assert np.array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    assert a["loss_mask"][:, -1].sum() == 0 and a["loss_mask"][:, :-1].min() == 1


@pytest.mark.parametrize("seed", [1, BIG])
def test_requests(gen, seed):
    m = mix("serve-backlog")
    a, b = gen.requests(m, seed, 3, 122753), gen.requests(m, seed, 3, 122753)
    assert a == b
    other = gen.requests(m, seed + 1, 3, 122753)
    assert a != other
    # every seed serves the same sizes in the same order, other tokens
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in other]
    assert all(p != q for (p, _), (q, _) in zip(a, other))
    prompts, outputs = gen.block_lengths(m)
    for k in range(3):  # every block holds the block's quantile sizes
        blk = a[8 * k: 8 * k + 8]
        assert sorted(len(p) for p, _ in blk) == sorted(prompts)
        assert sorted(n for _, n in blk) == sorted(outputs)
    assert all(16 <= len(p) <= 512 and 8 <= n <= 256 and len(p) + n <= 1024 for p, n in a)


def test_block_quantiles(gen):
    prompts, outputs = gen.block_lengths(mix("serve-backlog"))
    assert sorted(prompts) == prompts and sorted(outputs) == outputs
    assert prompts[len(prompts) // 2 - 1] <= 128 <= prompts[len(prompts) // 2]
    assert prompts[-1] == 512 and outputs[0] >= 8


def test_jax_seed_is_32_bit_and_stable(gen):
    assert gen.jax_seed(BIG) == gen.jax_seed(BIG) != gen.jax_seed(BIG + 1)
    assert 0 <= gen.jax_seed(2**40) < 2**32
