"""The one traffic generator: reads a mix file of parameters and makes
the inputs of a run from ``--seed``.

Every seed gets the same multiset of sizes and only another order (and
other token contents), so runs with different seeds do the same work.
A mix file says which of the three kinds it is:

* ``payloads`` -- allreduce launches: blocks holding every payload size
  once, each block in a seeded order.  A uniform draw over sizes in
  power-of-4 steps is a log-uniform draw over bytes.
* ``lm_batches`` -- training batches in ``SyntheticLM``'s arithmetic
  (zipf(1.3) unigrams with one repeated motif per row), one fresh batch
  per step.
* ``requests`` -- a closed serving backlog: blocks of ``block`` requests
  whose prompt and output lengths are the block's quantiles of two
  clipped lognormals.  Their pairing and order come from the mix's own
  ``order_seed``, the same for every run: the window holds only two or
  three blocks, so an order drawn per seed would change the work in it.
  ``--seed`` draws the tokens.
"""

from __future__ import annotations

import math
from collections.abc import Iterator
from statistics import NormalDist

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Generator for one named stream of one seed (any seed >= 0)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def jax_seed(seed: int) -> int:
    """A 32-bit key seed derived from a seed of any size."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


# --- allreduce payloads -----------------------------------------------------


def payload_sizes(mix: dict) -> list[int]:
    """Payload bytes per chip: min_bytes, x4, ..., max_bytes."""
    sizes, b = [], int(mix["min_bytes"])
    while b <= int(mix["max_bytes"]):
        sizes.append(b)
        b *= int(mix["step"])
    return sizes


def payload_schedule(mix: dict, seed: int) -> Iterator[int]:
    """Launch sizes, block after block with each size once per block,
    for as long as the window draws them."""
    sizes = payload_sizes(mix)
    r = rng(seed, 1)
    while True:
        yield from (sizes[i] for i in r.permutation(len(sizes)))


def payload_inputs(mix: dict, seed: int, nbytes: int, group: int):
    """Per-chip f32 operands of one payload size: the start value ``x``
    and the per-chip offset ``d`` of the chained loop (``d`` sums to
    about 0 over the chips, so the chain's mean stays put)."""
    n = max(1, nbytes // 4)
    r = rng(seed, 2, nbytes)
    x = r.standard_normal((group, n)).astype(np.float32)
    d = r.standard_normal((group, n))
    d = (d - d.mean(axis=0, keepdims=True)).astype(np.float32)
    return x, d


# --- training batches ---------------------------------------------------------


def lm_batch(mix: dict, seed: int, step: int, rows: int, vocab: int) -> dict:
    """Batch ``step``: ``rows`` sequences of ``mix['seq_len']`` tokens,
    labels shifted by one, the last position masked."""
    r = rng(seed, 3, step)
    S = int(mix["seq_len"])
    ranks = r.zipf(float(mix["zipf_a"]), size=(rows, S))
    tokens = np.minimum(ranks, vocab - 1).astype(np.int32)
    m = min(int(mix["motif_len"]), S // 2)
    motif = r.integers(0, vocab, size=(rows, m), dtype=np.int32)
    start = r.integers(0, max(1, S - 2 * m), size=rows)
    for b in range(rows):
        s0 = start[b]
        tokens[b, s0 : s0 + m] = motif[b]
        tokens[b, s0 + m : s0 + 2 * m] = motif[b]
    labels = np.concatenate([tokens[:, 1:], np.zeros((rows, 1), np.int32)], 1)
    mask = np.ones((rows, S), np.float32)
    mask[:, -1] = 0.0
    return {"tokens": tokens, "labels": labels, "loss_mask": mask}


# --- serving requests -----------------------------------------------------------


def _quantile_lengths(median, sigma, lo, hi, n) -> list[int]:
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return [int(min(hi, max(lo, round(median * math.exp(sigma * q))))) for q in z]


def block_lengths(mix: dict) -> tuple[list[int], list[int]]:
    """Prompt and output lengths of one block (before pairing)."""
    n = int(mix["block"])
    p, o = mix["prompt"], mix["output"]
    prompts = _quantile_lengths(p["median"], p["sigma"], p["min"], p["max"], n)
    outputs = _quantile_lengths(o["median"], o["sigma"], o["min"], o["max"], n)
    return prompts, outputs


def requests(mix: dict, seed: int, blocks: int, vocab: int) -> list[tuple[list[int], int]]:
    """``blocks`` blocks of ``(prompt tokens, output length)``."""
    prompts, outputs = block_lengths(mix)
    cap = int(mix["max_total"])
    order, toks = rng(int(mix["order_seed"]), 4), rng(seed, 6)
    out = []
    for _ in range(blocks):
        pi, oi = order.permutation(len(prompts)), order.permutation(len(outputs))
        for a, b in zip(pi, oi):
            n_out = min(outputs[b], cap - prompts[a])
            out.append((toks.integers(0, vocab, size=prompts[a]).tolist(), n_out))
    return out
