"""Beyond-paper extensions: NAP allgather and NAP reduce-scatter.

Paper §VI: "Natural extensions exist to the MPI_Allgather ... node-aware
extensions could be applied to larger MPI_Allreduce methods, optimizing
the reduce-scatter and allgather approach."  These implement exactly
that: the NAP exchange pattern applied to allgather (log_ppn(n)
inter-node steps instead of log2(n)) and to reduce-scatter (its mirror),
which together give a node-aware *large-message* allreduce whose
latency term is also log_ppn(n) — the missing piece the paper leaves as
future work.

Both require power-of-ppn node counts (the ragged donor machinery of the
allreduce does not transfer to value-carrying collectives); callers fall
back to XLA's native collectives otherwise via ``supported()``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import napalg
from .collectives import AxisNames, _as_tuple, _chip_index, _mask_lookup

__all__ = ["nap_allgather", "nap_reduce_scatter", "nap_allreduce_large", "supported"]


def _sizes(inter, intra):
    n = int(np.prod([lax.axis_size(a) for a in inter]))
    ppn = int(np.prod([lax.axis_size(a) for a in intra]))
    return n, ppn


def supported(n: int, ppn: int) -> bool:
    if n <= 1 or ppn < 2:
        return n > 0
    steps = napalg.nap_num_steps(n, ppn)
    return ppn**steps == n


def _step_masks(sched, n_chips):
    out = []
    for step in sched.steps:
        pairs = step.rounds[0]
        smask = np.zeros(n_chips, dtype=bool)
        for c in step.self_chips:
            smask[c] = True
        out.append((pairs, smask))
    return out


def nap_allgather(
    x: jax.Array, *, inter_axes: AxisNames, intra_axes: AxisNames
) -> jax.Array:
    """Node-aware allgather: returns (p, *x.shape) rows in chip order.

    log_ppn(n) inter-node exchange steps (payload growing ppn^i) versus
    log2(n) for recursive-doubling allgather.
    """
    inter, intra = _as_tuple(inter_axes), _as_tuple(intra_axes)
    n, ppn = _sizes(inter, intra)
    if not supported(n, ppn):
        raise ValueError(f"nap_allgather needs power-of-ppn nodes ({n},{ppn})")
    joint = inter + intra
    v = lax.all_gather(x, intra, axis=0)  # (ppn, ...)
    if n == 1:
        return v
    sched = napalg.build_nap_schedule(n, ppn)
    chip = _chip_index(inter, intra)
    for pairs, smask in _step_masks(sched, n * ppn):
        recv = lax.ppermute(v, joint, pairs)
        mine = _mask_lookup(smask, chip)
        recv = jnp.where(
            jnp.reshape(mine, (1,) * recv.ndim), v, recv
        )  # self-subgroup keeps its own block
        v = lax.all_gather(recv, intra, axis=0, tiled=True)
    return v


def nap_reduce_scatter(
    x: jax.Array, *, inter_axes: AxisNames, intra_axes: AxisNames
) -> jax.Array:
    """Node-aware reduce-scatter (sum): x is (p, ...) rows per chip;
    chip with flat id q returns the fully-reduced row q.

    Mirror of :func:`nap_allgather`: intra-node psum_scatter narrows the
    payload ppn-fold, one inter-node exchange per NAP level routes each
    block to the subgroup that owns it — log_ppn(n) inter-node steps.
    """
    inter, intra = _as_tuple(inter_axes), _as_tuple(intra_axes)
    n, ppn = _sizes(inter, intra)
    if not supported(n, ppn):
        raise ValueError(
            f"nap_reduce_scatter needs power-of-ppn nodes ({n},{ppn})"
        )
    joint = inter + intra
    chip = _chip_index(inter, intra)
    p = n * ppn
    if x.shape[0] != p:
        raise ValueError(f"leading dim {x.shape[0]} != total chips {p}")
    v = x
    if n > 1:
        sched = napalg.build_nap_schedule(n, ppn)
        for pairs, smask in reversed(_step_masks(sched, p)):
            v = lax.psum_scatter(v, intra, scatter_dimension=0, tiled=True)
            recv = lax.ppermute(v, joint, pairs)
            mine = _mask_lookup(smask, chip)
            v = jnp.where(jnp.reshape(mine, (1,) * recv.ndim), v, recv)
    v = lax.psum_scatter(v, intra, scatter_dimension=0, tiled=True)
    return v


def nap_allreduce_large(
    x: jax.Array, *, inter_axes: AxisNames, intra_axes: AxisNames
) -> jax.Array:
    """Node-aware large-message allreduce: NAP-RS + NAP-AG (§VI).

    Bandwidth-optimal data volume with only 2*log_ppn(n) inter-node
    message steps — the paper's proposed future-work algorithm.
    """
    inter, intra = _as_tuple(inter_axes), _as_tuple(intra_axes)
    n, ppn = _sizes(inter, intra)
    p = n * ppn
    orig_shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % p
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    rows = flat.reshape(p, -1)
    mine = nap_reduce_scatter(rows, inter_axes=inter, intra_axes=intra)
    full = nap_allgather(
        mine[0], inter_axes=inter, intra_axes=intra
    )
    out = full.reshape(-1)
    if pad:
        out = out[: out.size - pad]
    return out.reshape(orig_shape)
