"""shard_map implementations of the paper's allreduce algorithms.

Every function here is designed to be called *inside* a
``jax.shard_map``-traced function (or any context with named mesh axes).
The communication schedules are computed statically from the mesh axis
sizes (``jax.lax.axis_size``) by :mod:`repro.core.napalg`, then lowered to
``jax.lax.ppermute`` / ``psum`` calls — one ``collective-permute`` HLO per
inter-node step, which is exactly the quantity the paper minimizes.

TPU mapping (DESIGN.md §2): "node" = pod (ICI domain), "ppn" = chips per
pod, "inter-node network" = inter-pod DCI.  The same functions work for
any two-level mesh-axis hierarchy.

Algorithms:

* :func:`nap_allreduce` — the paper's contribution (§III): intra psum,
  ``ceil(log_ppn(n))`` joint-axis collective-permutes, intra psums.
* :func:`rd_allreduce` — node-agnostic recursive doubling (§II, Fig. 3).
* :func:`smp_allreduce` — MPICH's node-aware master-process algorithm
  (§II.A, Fig. 4).
* :func:`ring_allreduce` — bandwidth-optimal ring reduce-scatter +
  allgather (Patarasuk & Yuan, cited as [25]).
* :func:`rabenseifner_allreduce` — reduce-scatter + allgather via native
  XLA collectives (§II, [5], [8]); the "large message" baseline.
* :func:`mla_allreduce` — multi-lane node-aware allreduce: the pod partial
  is striped across local ranks (intra ``psum_scatter``), every lane runs
  reduce-scatter + allgather over the slow domain concurrently with
  ``s/ppn`` bytes, then an intra ``all_gather`` rebuilds the payload.  The
  bandwidth-regime engine (§VI future work, executed).  Supports
  ``op="sum"|"max"|"min"`` (dtype-aware pad identities) and
  ``pipeline_chunks=C``: the payload is split into ``C`` ragged chunks
  (``napalg.ragged_splits`` — no pad elements) whose independent
  collectives XLA can overlap, chunk ``c``'s inter-pod phase against
  chunk ``c+1``'s intra-pod phase.
* :func:`hierarchical_allreduce` — op-safe three-regime dispatcher: NAP
  for small payloads (latency regime), MLA for large ones (bandwidth
  regime, pipelined above the model's chunking threshold), plain psum
  when the mesh has no slow domain.  The NAP↔MLA switch point comes from
  the §IV cost model (:func:`perf_model.crossover_bytes`) for the actual
  grid shape, not a hardcoded constant; the MLA↔pipelined-MLA depth comes
  from :func:`perf_model.optimal_pipeline_chunks`.  Degenerate grids fall
  back identically in both threshold modes (fixed and modeled): ``psum``
  for ``n <= 1``, RS+AG for ``ppn == 1``.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import napalg
from ..runtime.tracing import scope

__all__ = [
    "nap_allreduce",
    "rd_allreduce",
    "smp_allreduce",
    "ring_allreduce",
    "rabenseifner_allreduce",
    "mla_allreduce",
    "mla_pipelined_allreduce",
    "mla_reduce_scatter",
    "mla_allgather",
    "flat_reduce_scatter",
    "flat_allgather",
    "hierarchical_allreduce",
    "select_algorithm",
    "auto_crossover_bytes",
    "ALGORITHMS",
]

AxisNames = str | tuple[str, ...]


def _as_tuple(axes: AxisNames) -> tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


# op registry: (pairwise fold, named-axis reduce, identity)
_OPS: dict[str, tuple[Callable, Callable, float]] = {
    "sum": (jnp.add, lax.psum, 0.0),
    "max": (jnp.maximum, lax.pmax, -jnp.inf),
    "min": (jnp.minimum, lax.pmin, jnp.inf),
}

# ops each bandwidth-regime engine can execute; the dispatcher never
# routes an op to an engine outside its set (op-safe dispatch)
_MLA_OPS = frozenset({"sum", "max", "min"})

# axis-wise reducers for the explicit (non-psum_scatter) reduce-scatter
_AXIS_REDUCERS: dict[str, Callable] = {
    "sum": jnp.sum,
    "max": jnp.max,
    "min": jnp.min,
}


def _op_identity(op: str, dtype) -> jax.Array:
    """Dtype-correct reduction identity (used for ragged padding).

    ``sum`` pads with zeros of the payload dtype; ``max``/``min`` use the
    dtype's own extremes — ``jnp.iinfo`` bounds for integers (a float
    ``-inf`` would silently promote integer payloads to float) and
    ``±inf`` for floats (representable in f32/bf16/f16).
    """
    dtype = jnp.dtype(dtype)
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return jnp.asarray(info.min if op == "max" else info.max, dtype)
    return jnp.asarray(-jnp.inf if op == "max" else jnp.inf, dtype)


def _needs_f32_accum(dtype) -> bool:
    """Whether cross-node sums of this dtype must accumulate in f32."""
    dt = jnp.dtype(dtype)
    return jnp.issubdtype(dt, jnp.floating) and dt.itemsize < 4


def _f32_fold(fold: Callable, op: str, dtype) -> Callable:
    """Pairwise fold that accumulates sub-f32 float sums in float32.

    The wire payload keeps its native dtype — upcast happens *after*
    receive and the result is downcast *before* the next send — so
    transport bytes are unchanged; only the local accumulate runs wide.
    ``max``/``min`` lose nothing to low precision and keep the plain
    fold.  This is the executed counterpart of the spmd-lint
    numerics-flow rule: a bf16 payload must never feed a cross-node
    reduction directly.
    """
    if op != "sum" or not _needs_f32_accum(dtype):
        return fold
    dtype = jnp.dtype(dtype)

    def wide_fold(a: jax.Array, b: jax.Array) -> jax.Array:
        return fold(
            a.astype(jnp.float32), b.astype(jnp.float32)
        ).astype(dtype)

    return wide_fold


def _chip_index(inter_axes: tuple[str, ...], intra_axes: tuple[str, ...]):
    """SMP-style flat chip id: node-major, local-rank-minor."""
    node = 0
    for ax in inter_axes:
        node = node * lax.axis_size(ax) + lax.axis_index(ax)
    rank = 0
    for ax in intra_axes:
        rank = rank * lax.axis_size(ax) + lax.axis_index(ax)
    ppn = int(np.prod([lax.axis_size(ax) for ax in intra_axes]))
    return node * ppn + rank


def _mask_lookup(mask: np.ndarray, chip) -> jax.Array:
    """Per-chip boolean from a host-side mask table (tiny constant)."""
    return jnp.asarray(mask)[chip]


# ---------------------------------------------------------------------------
# NAP allreduce — the paper's algorithm
# ---------------------------------------------------------------------------


def nap_allreduce(
    x: jax.Array,
    *,
    inter_axes: AxisNames,
    intra_axes: AxisNames,
    op: str = "sum",
) -> jax.Array:
    """Node-Aware Parallel allreduce (paper §III, Algorithm 1).

    Reduces ``x`` over the combined ``inter_axes x intra_axes`` device
    grid.  Each inter-node step lowers to a single ``collective-permute``
    over the *joint* axes (plus rare donor rounds for ragged node counts),
    so a chip sends at most ``ceil(log_ppn(n))`` inter-node messages —
    versus ``log2(n)`` for recursive doubling.

    Args:
      x: per-chip value (any shape); identical reduction returned on every
        chip of the grid.
      inter_axes: mesh axis name(s) spanning the *slow* domain (pods).
      intra_axes: mesh axis name(s) spanning the *fast* domain (chips
        within a pod).
      op: "sum" | "max" | "min".
    """
    inter, intra = _as_tuple(inter_axes), _as_tuple(intra_axes)
    fold, named_reduce, _ = _OPS[op]
    fold = _f32_fold(fold, op, x.dtype)
    n = int(np.prod([lax.axis_size(ax) for ax in inter]))
    ppn = int(np.prod([lax.axis_size(ax) for ax in intra]))
    sched = napalg.build_nap_schedule(n, ppn)
    joint = inter + intra

    with scope("intra", phase=True):
        v = named_reduce(x, intra)
    if not sched.steps:
        return v
    chip = _chip_index(inter, intra)
    # dtype-correct identity for every op: integer max/min must use the
    # iinfo extremes (a float ±inf identity silently promoted integer
    # payloads to float), and sum must stay in the payload dtype.
    ident = _op_identity(op, v.dtype)
    # Host-constant mask tables (cached per (n, ppn)) + a single masked
    # accumulation per round: the accumulator starts from the self
    # contribution instead of an identity-filled temporary, so each
    # inter-node step lowers to one select per round rather than the
    # full_like + where + fold chain per mask.
    for step, (rmasks, smask) in zip(
        sched.steps, napalg.step_mask_tables(n, ppn)
    ):
        with scope("inter", phase=True):
            acc = jnp.where(_mask_lookup(smask, chip), v, ident)
            for rnd, rmask in zip(step.rounds, rmasks):
                recv = lax.ppermute(v, joint, rnd)
                acc = jnp.where(
                    _mask_lookup(rmask, chip), fold(acc, recv), acc
                )
        with scope("intra", phase=True):
            v = named_reduce(acc, intra)
    return v


# ---------------------------------------------------------------------------
# point-to-point schedule executor (RD / SMP baselines)
# ---------------------------------------------------------------------------


def _run_p2p_schedule(
    x: jax.Array,
    sched: napalg.P2PSchedule,
    joint: tuple[str, ...],
    inter: tuple[str, ...],
    intra: tuple[str, ...],
    op: str,
) -> jax.Array:
    fold, _, _ = _OPS[op]
    fold = _f32_fold(fold, op, x.dtype)
    chip = _chip_index(inter, intra)
    v = x
    for step, rmask in zip(sched.steps, napalg.p2p_recv_masks(sched)):
        recv = lax.ppermute(v, joint, step.pairs)
        flag = _mask_lookup(rmask, chip)
        if step.combine:
            v = jnp.where(flag, fold(v, recv), v)
        else:
            v = jnp.where(flag, recv, v)
    return v


def rd_allreduce(
    x: jax.Array,
    *,
    inter_axes: AxisNames,
    intra_axes: AxisNames = (),
    op: str = "sum",
) -> jax.Array:
    """Node-agnostic recursive doubling over the flattened device grid.

    The classic butterfly (paper Fig. 3): ``log2(p)`` pairwise exchange
    steps, each lowering to one collective-permute.  Node-oblivious — at
    every inter-node step *all* chips of a node cross the slow domain with
    duplicate payloads, which is precisely the waste NAP removes.
    """
    inter, intra = _as_tuple(inter_axes), _as_tuple(intra_axes)
    joint = inter + intra
    n = int(np.prod([lax.axis_size(ax) for ax in inter]))
    ppn = int(np.prod([lax.axis_size(ax) for ax in intra])) if intra else 1
    sched = napalg.build_rd_schedule(n, ppn)
    return _run_p2p_schedule(x, sched, joint, inter, intra, op)


def smp_allreduce(
    x: jax.Array,
    *,
    inter_axes: AxisNames,
    intra_axes: AxisNames,
    op: str = "sum",
) -> jax.Array:
    """MPICH SMP allreduce (paper §II.A, Fig. 4).

    Local reduce to a master chip per pod, recursive doubling among the
    masters, local broadcast.  Same inter-node message *count* as RD but
    only one active chip per pod (no duplicate bytes, no injection
    pressure; all other chips idle — the imbalance NAP fixes).
    """
    inter, intra = _as_tuple(inter_axes), _as_tuple(intra_axes)
    joint = inter + intra
    n = int(np.prod([lax.axis_size(ax) for ax in inter]))
    ppn = int(np.prod([lax.axis_size(ax) for ax in intra]))
    sched = napalg.build_smp_schedule(n, ppn)
    return _run_p2p_schedule(x, sched, joint, inter, intra, op)


# ---------------------------------------------------------------------------
# bandwidth-regime baselines
# ---------------------------------------------------------------------------


def ring_allreduce(
    x: jax.Array, *, axes: AxisNames, op: str = "sum"
) -> jax.Array:
    """Bandwidth-optimal ring allreduce (reduce-scatter + allgather).

    ``2 (p-1)`` steps of neighbour exchange over the ring formed by the
    flattened ``axes``; each chip moves ``2 s (p-1)/p`` bytes — the data
    lower bound (paper §II, [25]).  Latency-poor for small ``s``.
    """
    fold, _, _ = _OPS[op]
    fold = _f32_fold(fold, op, x.dtype)
    ax = _as_tuple(axes)
    p = int(np.prod([lax.axis_size(a) for a in ax]))
    if p == 1:
        return x
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.size) % p
    if pad:
        # op-correct pad identity: zeros would corrupt max over
        # all-negative payloads (and min over all-positive ones)
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), _op_identity(op, flat.dtype))]
        )
    chunks = flat.reshape(p, -1)
    idx = 0
    for a in ax:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    fwd = [(i, (i + 1) % p) for i in range(p)]

    # reduce-scatter: after p-1 shifts, chip i owns the full sum of chunk
    # (i+1) mod p.
    def rs_body(k, carry):
        chunks, acc = carry
        send = lax.dynamic_index_in_dim(
            chunks, (idx - k) % p, axis=0, keepdims=False
        )
        payload = jnp.where(k == 0, send, acc)
        recv = lax.ppermute(payload, ax, fwd)
        own = lax.dynamic_index_in_dim(
            chunks, (idx - k - 1) % p, axis=0, keepdims=False
        )
        return chunks, fold(recv, own)

    _, acc = lax.fori_loop(0, p - 1, rs_body, (chunks, chunks[0]))

    # allgather ring: circulate the owned chunk p-1 times.
    def ag_body(k, carry):
        chunks, cur = carry
        recv = lax.ppermute(cur, ax, fwd)
        owner = (idx - k - 1) % p  # chunk id arriving at step k
        chunks = lax.dynamic_update_index_in_dim(
            chunks, recv, (owner + 1) % p, axis=0
        )
        return chunks, recv

    chunks = lax.dynamic_update_index_in_dim(
        chunks, acc, (idx + 1) % p, axis=0
    )
    chunks, _ = lax.fori_loop(0, p - 1, ag_body, (chunks, acc))
    out = chunks.reshape(-1)
    if pad:
        out = out[: out.size - pad]
    return out.reshape(orig_shape).astype(orig_dtype)


def rabenseifner_allreduce(
    x: jax.Array, *, axes: AxisNames, op: str = "sum"
) -> jax.Array:
    """Reduce-scatter + allgather via native XLA collectives ([5], [8]).

    Optimal data transport with ``2 log2(p)`` message steps; the paper's
    recommended regime for reductions above ~2 KiB.  For ``sum`` XLA
    emits ``reduce-scatter`` + ``all-gather`` directly, so on TPU this
    also enjoys ICI pipelining.  ``max``/``min`` (which
    ``lax.psum_scatter`` cannot express) realize the reduce-scatter as
    ``all_to_all`` + a local fold — identical byte transport
    (``(p-1)/p * s`` each way) — with dtype-correct pad identities.
    """
    if op not in _MLA_OPS:
        raise NotImplementedError(
            f"rabenseifner path supports {sorted(_MLA_OPS)}, got {op!r}"
        )
    ax = _as_tuple(axes)
    p = int(np.prod([lax.axis_size(a) for a in ax]))
    if p == 1:
        return x
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.size) % p
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), _op_identity(op, flat.dtype))]
        )
    tiles = flat.reshape(p, -1)
    if op == "sum" and not _needs_f32_accum(flat.dtype):
        shard = lax.psum_scatter(flat, ax, scatter_dimension=0, tiled=True)
    else:
        # reduce-scatter(max/min): every chip scatters tile j to chip j,
        # receives all chips' copies of its own tile, folds locally.
        # Sub-f32 float sums take the same route so the fold can run in
        # f32 (psum_scatter would accumulate on the wire dtype) — the
        # transport stays at native width either way.
        gathered = lax.all_to_all(
            tiles[:, None, :], ax, split_axis=0, concat_axis=1, tiled=False
        )
        if op == "sum":
            shard = (
                gathered[0].astype(jnp.float32).sum(axis=0)
            ).astype(flat.dtype)
        else:
            shard = _AXIS_REDUCERS[op](gathered[0], axis=0)
    out = lax.all_gather(shard, ax, axis=0, tiled=True)
    if pad:
        out = out[: out.size - pad]
    return out.reshape(orig_shape).astype(orig_dtype)


# ---------------------------------------------------------------------------
# MLA allreduce — multi-lane node-aware bandwidth path
# ---------------------------------------------------------------------------


def _mla_one_chunk(
    flat: jax.Array,
    inter: tuple[str, ...],
    intra: tuple[str, ...],
    n: int,
    ppn: int,
    op: str,
) -> jax.Array:
    """One chunk of the MLA allreduce (flat 1-D payload in, same out)."""
    size = flat.size
    pad = (-size) % ppn
    if pad:
        # the pad identity never crosses the slow domain logically: the
        # ragged schedule/accounting (napalg.mla_stripe_geometry) charges
        # only real elements, and the identity is op/dtype-correct so the
        # result is exact either way
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), _op_identity(op, flat.dtype))]
        )
    tiles = flat.reshape(ppn, -1)
    # phase 1: stripe the pod partial across local ranks
    with scope("intra", phase=True):
        if op == "sum":
            stripe = lax.psum_scatter(
                flat, intra, scatter_dimension=0, tiled=True
            )
        else:
            gathered = lax.all_to_all(
                tiles[:, None, :], intra, split_axis=0, concat_axis=1,
                tiled=False,
            )
            stripe = _AXIS_REDUCERS[op](gathered[0], axis=0)
    # phase 2: per-lane RS+AG across the slow domain (ppn parallel lanes)
    if n > 1:
        with scope("inter", phase=True):
            stripe = rabenseifner_allreduce(stripe, axes=inter, op=op)
    # phase 3: rebuild the full payload inside the pod
    with scope("intra", phase=True):
        out = lax.all_gather(stripe, intra, axis=0, tiled=True)
    if pad:
        out = out[: out.size - pad]
    return out


def mla_allreduce(
    x: jax.Array,
    *,
    inter_axes: AxisNames,
    intra_axes: AxisNames,
    op: str = "sum",
    pipeline_chunks: int = 1,
) -> jax.Array:
    """Multi-lane node-aware allreduce (the bandwidth-regime engine).

    Three phases, mirroring :func:`napalg.build_mla_schedule`:

      1. intra-pod reduce-scatter stripes the pod-local partial across
         the ``ppn`` local ranks — rank ``r`` owns stripe ``r`` of
         ``s/ppn`` bytes (``psum_scatter`` for sum; ``all_to_all`` + a
         local fold for max/min, same byte transport);
      2. every lane ``r`` runs an independent reduce-scatter + allgather
         over ``inter_axes`` — all ``ppn`` lanes cross the slow domain
         concurrently with ``s/ppn`` bytes each, instead of every chip
         carrying the full ``s`` (the §II duplicate-traffic waste) or a
         single master serialising the node's bandwidth (SMP);
      3. intra-pod ``all_gather`` rebuilds the full reduced payload.

    Per-chip inter-node traffic is ``~2*(s/ppn)*(n-1)/n`` — the data lower
    bound divided across all local ranks — which is why this wins the
    large-message regime the paper's §VI leaves as future work.

    ``pipeline_chunks=C > 1`` splits the payload into ``C`` *ragged*
    chunks (:func:`napalg.ragged_splits` — uneven sizes, no pad elements
    at the chunk level) and runs the three phases per chunk.  The chunks
    carry no data dependencies on each other, so XLA's async collectives
    can overlap chunk ``c``'s inter-pod phase with chunk ``c±1``'s
    intra-pod phases (ICI vs DCI — distinct networks), the chunk-level
    overlap of Träff's doubly-pipelined scheme.  The model-optimal depth
    comes from :func:`perf_model.optimal_pipeline_chunks`; the ``auto``
    dispatcher applies it for payloads past the chunking threshold.
    """
    if op not in _MLA_OPS:
        raise NotImplementedError(
            f"mla path supports {sorted(_MLA_OPS)}, got {op!r}"
        )
    inter, intra = _as_tuple(inter_axes), _as_tuple(intra_axes)
    ppn = int(np.prod([lax.axis_size(ax) for ax in intra]))
    n = int(np.prod([lax.axis_size(ax) for ax in inter]))
    if ppn == 1:
        return rabenseifner_allreduce(x, axes=inter, op=op)
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    chunks = max(1, min(int(pipeline_chunks), flat.size))
    if chunks == 1:
        out = _mla_one_chunk(flat, inter, intra, n, ppn, op)
        return out.reshape(orig_shape).astype(orig_dtype)
    parts = []
    off = 0
    for ce in napalg.ragged_splits(flat.size, chunks):
        if ce == 0:
            continue
        parts.append(
            _mla_one_chunk(flat[off : off + ce], inter, intra, n, ppn, op)
        )
        off += ce
    out = jnp.concatenate(parts)
    return out.reshape(orig_shape).astype(orig_dtype)


def mla_pipelined_allreduce(
    x: jax.Array,
    *,
    inter_axes: AxisNames,
    intra_axes: AxisNames,
    op: str = "sum",
    pipeline_chunks: int | None = None,
    params=None,
) -> jax.Array:
    """MLA with the pipeline depth solved from the §IV cost model.

    ``pipeline_chunks=None`` asks :func:`perf_model.optimal_pipeline_chunks`
    for the depth that balances the extra per-chunk alpha steps against
    the intra/inter overlap for this payload and grid — the same decision
    the simulator replays and ``select_algorithm`` dispatches on.  Pass
    the same ``params`` (MachineParams) given to ``select_algorithm`` so
    the dispatch decision and the executed depth are solved under one
    machine model (default: TPU_V5E_POD, matching the dispatcher).
    """
    if pipeline_chunks is None:
        from . import perf_model as pm

        inter, intra = _as_tuple(inter_axes), _as_tuple(intra_axes)
        n = int(np.prod([lax.axis_size(ax) for ax in inter]))
        ppn = int(np.prod([lax.axis_size(ax) for ax in intra]))
        nbytes = float(int(np.prod(x.shape)) * x.dtype.itemsize)
        pipeline_chunks = pm.optimal_pipeline_chunks(
            nbytes, n, ppn, params or pm.TPU_V5E_POD
        )
    return mla_allreduce(
        x,
        inter_axes=inter_axes,
        intra_axes=intra_axes,
        op=op,
        pipeline_chunks=pipeline_chunks,
    )


# ---------------------------------------------------------------------------
# reduce-scatter / allgather — first-class striped collectives
# ---------------------------------------------------------------------------


def _level_reduce_scatter(
    flat: jax.Array, axes, k: int, op: str, *, f32_accum: bool = False
) -> jax.Array:
    """One reduce-scatter level: pad to ``k``, scatter tile ``i`` to the
    rank of index ``i`` along ``axes`` (psum_scatter for sum, all_to_all
    + fold for max/min — same byte transport).

    ``f32_accum=True`` marks a level that crosses the slow domain: a
    sub-f32 float sum then routes through ``all_to_all`` + an f32 fold
    (native wire width, wide accumulate) instead of letting
    ``psum_scatter`` accumulate on the wire dtype.
    """
    if k <= 1:
        return flat
    pad = (-flat.size) % k
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), _op_identity(op, flat.dtype))]
        )
    tiles = flat.reshape(k, -1)
    wide = f32_accum and op == "sum" and _needs_f32_accum(flat.dtype)
    if op == "sum" and not wide:
        return lax.psum_scatter(flat, axes, scatter_dimension=0, tiled=True)
    gathered = lax.all_to_all(
        tiles[:, None, :], axes, split_axis=0, concat_axis=1, tiled=False
    )
    if wide:
        return (
            gathered[0].astype(jnp.float32).sum(axis=0)
        ).astype(flat.dtype)
    return _AXIS_REDUCERS[op](gathered[0], axis=0)


def mla_reduce_scatter(
    x: jax.Array,
    *,
    inter_axes: AxisNames,
    intra_axes: AxisNames,
    op: str = "sum",
) -> jax.Array:
    """Node-aware striped reduce-scatter — the RS half of the MLA
    allreduce, promoted to a public collective.

    Two levels: the pod partial is striped across the ``ppn`` local
    lanes (intra reduce-scatter), then every lane reduce-scatters its
    stripe over the slow domain — chip ``(node j, lane r)`` ends up
    owning the fully reduced block ``(r, j)`` of the MLA stripe layout
    (:func:`napalg.mla_stripe_geometry`, uniform-padded for SPMD shape
    agreement like the MLA lowering).  Per-chip inter-node bytes are
    half the allreduce round trip — the ZeRO-style sharded-optimizer
    sync primitive.  Inverse: :func:`mla_allgather`.
    """
    if op not in _MLA_OPS:
        raise NotImplementedError(
            f"mla_reduce_scatter supports {sorted(_MLA_OPS)}, got {op!r}"
        )
    inter, intra = _as_tuple(inter_axes), _as_tuple(intra_axes)
    ppn = int(np.prod([lax.axis_size(ax) for ax in intra])) if intra else 1
    n = int(np.prod([lax.axis_size(ax) for ax in inter])) if inter else 1
    flat = x.reshape(-1)
    with scope("intra", phase=True):
        stripe = _level_reduce_scatter(flat, intra, ppn, op)
    with scope("inter", phase=True):
        return _level_reduce_scatter(stripe, inter, n, op, f32_accum=True)


def mla_allgather(
    x: jax.Array,
    *,
    inter_axes: AxisNames,
    intra_axes: AxisNames,
    elems: int | None = None,
) -> jax.Array:
    """Node-aware striped allgather — the AG half of the MLA allreduce.

    Exact inverse of :func:`mla_reduce_scatter` on the same topology:
    every lane allgathers its blocks over the slow domain (rebuilding
    its stripe), then an intra-pod allgather rebuilds the flat payload.
    ``elems`` is the original payload size, needed to strip the
    uniform-shape padding (default: assume no padding was required).
    """
    inter, intra = _as_tuple(inter_axes), _as_tuple(intra_axes)
    ppn = int(np.prod([lax.axis_size(ax) for ax in intra])) if intra else 1
    n = int(np.prod([lax.axis_size(ax) for ax in inter])) if inter else 1
    shard = x.reshape(-1)
    if elems is None:
        elems = shard.size * n * ppn
    stripe_len = -(-int(elems) // ppn)  # ceil: the intra-RS stripe size
    if n > 1:
        with scope("inter", phase=True):
            stripe = lax.all_gather(shard, inter, axis=0, tiled=True)
        stripe = stripe[:stripe_len]
    else:
        stripe = shard[:stripe_len]
    if ppn > 1:
        with scope("intra", phase=True):
            full = lax.all_gather(stripe, intra, axis=0, tiled=True)
    else:
        full = stripe
    return full[: int(elems)]


def flat_reduce_scatter(
    x: jax.Array, *, axes: AxisNames, op: str = "sum", f32_accum: bool = False
) -> jax.Array:
    """Single-level (node-agnostic) reduce-scatter over the flattened
    ``axes`` grid — the fallback engine when there is no slow domain.
    ``f32_accum=True`` (set by the dispatcher when the flattened grid
    does cross nodes) keeps sub-f32 sums accumulating in f32."""
    if op not in _MLA_OPS:
        raise NotImplementedError(
            f"flat_reduce_scatter supports {sorted(_MLA_OPS)}, got {op!r}"
        )
    ax = _as_tuple(axes)
    p = int(np.prod([lax.axis_size(a) for a in ax])) if ax else 1
    return _level_reduce_scatter(x.reshape(-1), ax, p, op, f32_accum=f32_accum)


def flat_allgather(
    x: jax.Array, *, axes: AxisNames, elems: int | None = None
) -> jax.Array:
    """Single-level allgather over the flattened ``axes`` grid — inverse
    of :func:`flat_reduce_scatter` (chip-order tile layout)."""
    ax = _as_tuple(axes)
    p = int(np.prod([lax.axis_size(a) for a in ax])) if ax else 1
    shard = x.reshape(-1)
    if p <= 1:
        out = shard
    else:
        out = lax.all_gather(shard, ax, axis=0, tiled=True)
    if elems is None:
        elems = shard.size * p
    return out[: int(elems)]


# ---------------------------------------------------------------------------
# dispatcher — thin delegates over the engine registry (repro.core.comm)
# ---------------------------------------------------------------------------


def _psum_allreduce(x, *, inter_axes, intra_axes=(), op="sum", **_):
    _, named_reduce, _ = _OPS[op]
    inter = _as_tuple(inter_axes)
    joint = inter + _as_tuple(intra_axes)
    if op == "sum" and inter and _needs_f32_accum(x.dtype):
        # the native psum accumulates on the wire dtype; a cross-node
        # bf16 sum must run in f32 (spmd-lint numerics-flow rule)
        return named_reduce(x.astype(jnp.float32), joint).astype(x.dtype)
    return named_reduce(x, joint)


def __getattr__(name: str):
    # ``ALGORITHMS`` is a *view* of the engine registry now — the
    # registry (repro.core.comm) is the single source of truth, and this
    # legacy alias stays importable for existing callers.
    if name == "ALGORITHMS":
        from . import comm

        return comm.legacy_execute_table()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@functools.lru_cache(maxsize=None)
def auto_crossover_bytes(n: int, ppn: int, params=None) -> float:
    """Model-driven NAP↔MLA crossover for an (n, ppn) grid (cached).

    Legacy alias of :meth:`repro.core.comm.Topology.crossover_bytes` —
    solved from the §IV max-rate cost model for the actual grid shape
    and machine constants, never a hardcoded byte count.

    Returns ``math.inf`` when NAP never loses within the model's search
    range (saturated crossover — machines whose alpha bill dwarfs the
    bandwidth term).  Callers must treat infinity as "latency regime for
    every payload", not clamp it to a byte count: the dispatch then
    routes everything to NAP, and the grad-sync planner keeps its
    *fusion* bucket target on the separate
    :func:`perf_model.optimal_bucket_bytes` optimum, which stays finite.
    """
    from . import comm

    return comm.Topology.of(n, ppn, params=params).crossover_bytes()


def select_algorithm(
    nbytes: int,
    n: int,
    ppn: int,
    params=None,
    op: str = "sum",
    small_threshold_bytes: int | None = None,
) -> str:
    """The op-safe three-regime dispatch decision (host-side, static).

    Legacy wrapper over :func:`repro.core.comm.select_engine` — the
    capability-filtered cost tournament over the registered engines:

    * no slow domain (``n <= 1``) — "psum": single-level native reduce;
    * ``ppn == 1`` — "mla" (degenerates to RS+AG over the slow domain):
      NAP needs ``ppn >= 2`` to trade steps for lanes, in *both*
      threshold modes;
    * ``nbytes`` at or below the crossover — "nap": latency regime,
      ``log_ppn(n)`` inter-node steps;
    * above it — the bandwidth tournament: "mla_pipelined" when chunked
      intra/inter overlap strictly beats plain MLA under the declared
      cost models, plain "mla" otherwise.

    ``op`` guards the decision through the engines' declared capability
    sets — dispatch cannot route a payload to an engine that would raise
    at trace time.  ``small_threshold_bytes`` overrides the modeled
    crossover with a fixed byte threshold; the degenerate-grid fallbacks
    above apply identically.
    """
    from . import comm

    return comm.select_engine(
        comm.Topology.of(n, ppn, params=params),
        int(nbytes),
        op=op,
        small_threshold_bytes=small_threshold_bytes,
    ).engine


def hierarchical_allreduce(
    x: jax.Array,
    *,
    inter_axes: AxisNames,
    intra_axes: AxisNames,
    algorithm: str = "auto",
    op: str = "sum",
    small_threshold_bytes: int | None = None,
    pipeline_chunks: int | None = None,
) -> jax.Array:
    """Allreduce over a two-level hierarchy with a model-driven switch.

    .. deprecated::
        Thin shim over the topology-first API: builds a
        :class:`repro.core.comm.Topology` from the axis names and a
        default policy, then calls
        :meth:`repro.core.comm.CommContext.allreduce`.  Warns once.

    ``algorithm="auto"`` runs the engine-registry dispatch (NAP below
    the modeled NAP↔MLA crossover, the striped multi-lane MLA path above
    it — chunk-pipelined when the cost tournament says the payload
    amortises the extra latency steps — plain psum when there is no slow
    domain), op-aware through the engines' declared capability sets.
    ``small_threshold_bytes`` overrides the modeled crossover;
    ``pipeline_chunks`` pins the MLA pipeline depth.
    """
    from . import comm

    comm.warn_deprecated_once(
        "collectives.hierarchical_allreduce", "CommContext.allreduce"
    )
    ctx = comm.CommContext(
        comm.Topology.from_axes(inter_axes, intra_axes),
        comm.CommPolicy(
            algorithm=algorithm,
            small_threshold_bytes=small_threshold_bytes,
            pipeline_chunks=pipeline_chunks,
        ),
    )
    return ctx.allreduce(x, op=op)
