"""Plain reference of the allreduce cell's chained loop, on the host.

Each of ``calls`` steps replaces every chip's operand by the sum over
the chips, divided by 4, plus that chip's own offset ``d``.  ``x`` and
``d`` are ``(chips, n)``; every operation is rounded to ``dtype``.
"""

from __future__ import annotations

import numpy as np


def chain(x, d, calls: int, dtype=np.float64):
    y = x.astype(dtype)
    d = d.astype(dtype)
    q = dtype(0.25)
    for _ in range(calls):
        y = (y.sum(axis=0, keepdims=True, dtype=dtype).astype(dtype) * q).astype(dtype) + d
        y = y.astype(dtype)
    return y


def rel_err(got, want) -> float:
    """Largest gap over all chips and elements, over the largest |want|."""
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max())
