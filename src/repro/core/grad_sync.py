"""Gradient synchronisation: bucket-scheduled collectives of a pytree.

Since PR 4 this module is the *executor* half of the grad-sync subsystem,
driven by the topology-first API (:mod:`repro.core.comm`):

* the **context** (:class:`comm.CommContext` = :class:`comm.Topology` +
  :class:`comm.CommPolicy`) owns the grid shape, the machine model and
  the dispatch policy — no ``(inter_axes, intra_axes, n, ppn, params)``
  keyword soup;
* the **planner** (:func:`repro.core.bucketing.plan_buckets`) packs
  leaves into size-targeted, dtype-pure buckets whose size optimum comes
  from :meth:`comm.Topology.optimal_bucket_bytes` and whose boundaries
  are snapped to the ragged pipeline-chunk grid;
* the **executor** (this module) issues buckets in reverse-leaf order
  with each bucket's engine and pipeline depth pinned by the planner —
  inside SPMD the interleaved issue order feeds XLA's latency-hiding
  scheduler independent collectives (bucket-level async);
* the **simulator** (:func:`repro.core.simulator.simulate_bucketed_sync`)
  replays the same plan with a compute port.

Two sync routes:

* :func:`CommContext.sync_grads` / :func:`sync_grads_local` — replicated
  allreduce sync (every chip gets the full averaged gradients);
* :func:`sync_grads_sharded` — ZeRO-style sharded sync: each leaf is
  reduce-scattered and every chip keeps only its 1-D shard (its
  optimizer partition's slice), halving per-chip inter-node bytes;
  :func:`unshard_grads` allgathers back when needed.

Optional *quantised gradient compression* (``compress_bits=8`` → int8
wire, ``compress_bits=4`` → two int4 nibbles packed per byte) runs on
the fused Pallas transport kernels
(:mod:`repro.kernels.transport`): per-leaf max-abs scales are agreed in
one NAP-pmax collective, then each transport hop is **one
quantize-pack kernel pass** writing wire bytes directly in stripe
layout.  The collective shape is a node-aware two-level exchange —
exact f32 intra-node ``psum_scatter`` pre-combine, packed inter-node
``all_to_all`` + local fold (the RS half), requantize at the group
bound, packed inter-node ``all_gather`` + unpack (the AG half), intra
``all_gather`` — so per-chip inter-node bytes are
``~2 * (s * bits/8 / ppn) * (n-1)/n``: 1/4 of uncompressed f32 at 8
bits, 1/8 at packed 4 bits.  The planner budgets compressed leaves at
the *packed* width (``bits/8`` bytes/elem) so the regime decision sees
the bytes that actually move, and **error-feedback residuals**
(:mod:`repro.optim.error_feedback`, threaded via
``sync_with_context(..., ef_state=...)``) carry each chip's
quantization error into its next step so 4-bit transport converges.
:func:`compressed_transport_dtype` remains the overflow-safe
*accumulator* width for summing quantised values outside the packed
engine (analysis + legacy callers).

:class:`GradSyncConfig` is kept as a deprecated alias of
:class:`comm.CommPolicy` (warns once): it still works everywhere, but
new code should build a ``Topology`` + ``CommContext`` instead.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from . import bucketing, collectives, comm
from ..kernels import transport
from ..runtime.tracing import scope

__all__ = [
    "GradSyncConfig",
    "sync_grads_local",
    "sync_with_context",
    "sync_grads_sharded",
    "unshard_grads",
    "make_grad_sync",
    "plan_for_tree",
    "compressed_transport_dtype",
]


@dataclasses.dataclass(frozen=True)
class GradSyncConfig(comm.CommPolicy):
    """Deprecated alias of :class:`repro.core.comm.CommPolicy`.

    .. deprecated::
        Thin shim kept for existing callers — constructing one warns
        once and behaves exactly like a ``CommPolicy``; the sync entry
        points build a :class:`comm.Topology` + :class:`comm.CommContext`
        from it internally.  New code: ``CommContext(topology,
        CommPolicy(...)).sync_grads(grads)``.

    algorithm: "auto" (model-driven dispatch) or a registered allreduce
      engine — "nap" | "rd" | "smp" | "mla" | "mla_pipelined" | "psum" |
      "ring" | "rabenseifner".  Validated at construction: a typo raises
      immediately with the list of registered engines instead of a bare
      ``KeyError`` deep inside tracing.
    mean: divide by the DP group size (data-parallel averaging).  Applies
      to *every* leaf: integer gradients are averaged in float32 and
      rounded back to their dtype rather than silently left as sums.
    compress_bits: None (off) or 2..8 — quantised transport on the fused
      Pallas kernels with per-leaf max-abs scales; 8 moves int8 wire
      bytes (1/4 of f32), 4 packs two nibbles per byte (1/8).
    small_threshold_bytes: NAP↔MLA dispatch crossover override.  ``None``
      (default) derives it from the §IV cost model for the actual grid —
      possibly ``inf`` when NAP never loses (saturated crossover).
    fuse_small_buckets: let the planner fuse same-dtype float leaves into
      shared buckets (False = one bucket per leaf).
    bucket_bytes: fusion bucket size target.  ``None`` (default) takes
      the overlap optimum from :meth:`comm.Topology.optimal_bucket_bytes`;
      an int pins it.
    pipeline_chunks: MLA pipeline depth for bandwidth-regime buckets.
      ``None`` (default) lets the model pick per bucket; an int pins it.
    """

    def __post_init__(self):
        comm.warn_deprecated_once(
            "grad_sync.GradSyncConfig",
            "comm.CommPolicy with comm.CommContext",
        )
        super().__post_init__()


def compressed_transport_dtype(group: int, bits: int) -> jnp.dtype:
    """Narrowest integer dtype that can hold a ``group``-way sum of
    ``bits``-bit quantised values without overflow.

    Quantised magnitudes are bounded by ``qmax = 2**(bits-1) - 1``, so
    the reduced sum is bounded by ``group * qmax``: int8 suffices only
    for a single rank, int16 up to 257-way groups (257 * 127 = 32639),
    int32 beyond.  Transporting int16 instead of the old int32 halves
    the bytes the "compressed" path actually moves.

    Groups too large even for int32 (> ~16.9M ranks at 8 bits) would
    need int64 — which jax silently degrades to int32 when x64 is
    disabled (the default), re-introducing the exact overflow this
    function exists to prevent.  That case raises ``OverflowError``
    instead of returning a dtype the runtime won't honor; chunk the
    reduction (hierarchical partial sums) or enable ``jax_enable_x64``.
    """
    qmax = 2 ** (bits - 1) - 1
    peak = max(1, int(group)) * qmax
    for dt in (jnp.int8, jnp.int16, jnp.int32):
        if peak <= jnp.iinfo(dt).max:
            return jnp.dtype(dt)
    if not jax.config.jax_enable_x64:
        raise OverflowError(
            f"a {group}-way sum of {bits}-bit quantised values overflows "
            "int32, and jax x64 is disabled so an int64 transport would "
            "silently degrade to int32 — re-introducing the overflow. "
            "Chunk the reduction into sub-groups or enable "
            "jax.config.jax_enable_x64."
        )
    return jnp.dtype(jnp.int64)


# ---------------------------------------------------------------------------
# per-payload reduction primitives (context-driven)
# ---------------------------------------------------------------------------


def _one_allreduce(x, ctx: comm.CommContext):
    topo = ctx.topology
    if not topo.inter_axes:
        # single-level mesh: no slow domain; plain psum over the DP axes.
        return lax.psum(x, topo.intra_axes)
    return ctx.allreduce(x)


def _agreed_absmax(parts, ctx: comm.CommContext):
    """Per-part max-abs scales agreed across the group in ONE fused
    small-vector max-allreduce (the paper's canonical latency-bound
    workload) — never one collective per leaf."""
    topo = ctx.topology
    absmax = jnp.stack(
        [jnp.max(jnp.abs(p)).astype(jnp.float32) for p in parts]
    )
    if topo.inter_axes:
        return collectives.nap_allreduce(
            absmax,
            inter_axes=topo.inter_axes,
            intra_axes=topo.intra_axes,
            op="max",
        )
    return lax.pmax(absmax, topo.intra_axes)


def _wire_split(topo: comm.Topology):
    """(pre_axes, wire_axes, pre, g): the f32 pre-combine domain and the
    packed-wire exchange domain of the compressed transport.

    With a slow domain the node is the pre-combine (exact f32
    ``psum_scatter`` over ``ppn`` lanes) and the wire crosses nodes —
    anything else would move ``ppn``× more inter-node bytes than the
    node-aware shape.  Degenerate grids collapse a level:
    single-lane nodes wire over ``inter`` alone, single-node meshes wire
    over ``intra``.  Always ``pre * g == group``.
    """
    if topo.n_nodes > 1 and topo.ppn > 1:
        return topo.intra_axes, topo.inter_axes, topo.ppn, topo.n_nodes
    if topo.n_nodes > 1:
        return (), topo.inter_axes, 1, topo.n_nodes
    return (), topo.intra_axes, 1, topo.ppn


def _flat_index(axes) -> jax.Array:
    idx = jnp.zeros((), jnp.int32)
    for ax in axes:
        idx = idx * lax.axis_size(ax) + lax.axis_index(ax)
    return idx


def _leaf_offsets(parts) -> tuple[int, ...]:
    offs, off = [], 0
    for p in parts:
        offs.append(off)
        off += int(p.size)
    return tuple(offs)


def _wire_scale(x, offsets, sizes, base, wire_axes, qmax):
    """Agreed (L,) per-leaf wire scale for the window ``[base, base+|x|)``
    of the fused flat payload: masked absmax of ``x`` per leaf, maxed
    over the wire group (every peer quantizes/dequantizes the same hop
    with the same scales), divided by ``qmax``.  Leaves outside the
    window get the 1e-30 floor — they carry no data on this hop."""
    idx = base + jnp.arange(int(x.size), dtype=jnp.int32)
    ax = jnp.abs(x.reshape(-1))
    m = jnp.stack([
        jnp.max(jnp.where((idx >= o) & (idx < o + n), ax, 0.0))
        for o, n in zip(offsets, sizes)
    ])
    if wire_axes:
        m = lax.pmax(m, wire_axes)
    return jnp.maximum(m / qmax, 1e-30)


def _compressed_fused_allreduce(
    parts, ctx: comm.CommContext, group, with_err=False
):
    """Quantised allreduce of one or more fused parts with *per-leaf*
    scales, on the fused Pallas transport kernels.

    One shared max-abs scale across a whole fused bucket would be set by
    its largest-magnitude leaf, rounding a small-magnitude neighbour
    (layer-norm grads next to embedding grads) entirely to zero.  Each
    leaf keeps its own scale: the per-leaf absmaxes travel as one fused
    max-allreduce, and every transport hop quantizes/unpacks all leaf
    segments in a single kernel pass (leaf boundaries are static index
    maps, not per-leaf launches).  Two-level shape — see the module
    docstring; ``pallas_call`` count per bucket is exactly 4 regardless
    of how many leaves the bucket fuses (quantize-stripe, unpack on
    receive, requantize at the group bound, unpack after allgather).

    Scale plumbing (``qmax = 2**(bits-1)-1``): each hop quantizes at the
    *measured* per-leaf absmax of what actually goes on the wire — the
    post-pre-combine stripe for hop 1, the RS-half fold for hop 2 —
    agreed across the wire group as one fused (L,) ``pmax`` per hop.
    The analytic bounds (stripe ≤ ``pre*A``, fold ≤ ``group*A`` with
    ``A`` the leaf absmax) hold but are worst-case by the full fan-in;
    quantizing at them would burn ~``log2(group)`` of the wire's
    ``bits`` on headroom real sums never use.  Total absolute error
    stays ≤ ``group*A/qmax`` (measured scales only shrink it).

    With ``with_err=True`` the call also returns the chip's share of the
    rounding error, *measured at the two compression points*: the hop-1
    error ``stripe - dequant(Q(stripe))`` on the chip's own stripe and
    the hop-2 error ``blk - dequant(Q(blk))`` on the block it owns.
    Every coordinate's total error is split across the group with each
    piece held by exactly one chip (stripe owner per node + one block
    owner), so re-injecting it into next step's input (``c = g + r``)
    compensates the full quantisation error — this is exact distributed
    error feedback, not a per-chip model of it.  The error is computed
    with the pure-jnp reference path (``impl="xla"``) so EF adds zero
    ``pallas_call`` sites: the fused count stays 4 per bucket.

    Returns ``(outs, scales, err)``: per-leaf float32 *sums* in
    ``parts`` order, the (L,) hop-1 wire scales, and the flat (E,)
    per-chip error (``None`` unless ``with_err``).
    """
    bits = ctx.policy.compress_bits
    qmax = float(2 ** (bits - 1) - 1)
    offsets = _leaf_offsets(parts)
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    flat = flat.astype(jnp.float32)
    E = int(flat.size)

    def split(full):
        outs = []
        for i, p in enumerate(parts):
            outs.append(full[offsets[i] : offsets[i] + p.size])
        return outs

    sizes = tuple(int(p.size) for p in parts)

    # rows handed to the kernels are whole transport blocks: the kernels
    # pad every row to one anyway, and padding a ragged 2-D row makes
    # XLA:TPU relayout it (and compile for seconds per million elements)
    unit = transport.DEFAULT_BLOCK
    if group <= 1:
        # single chip: no wire — but keep the quantize round trip so the
        # compression semantics (and EF residuals) match any grid size
        scales = _wire_scale(flat, offsets, sizes, 0, (), qmax)
        Ep = -(-E // unit) * unit
        padded = flat
        if Ep != E:
            padded = jnp.concatenate([flat, jnp.zeros((Ep - E,), jnp.float32)])
        w = transport.quantize_pack(
            padded.reshape(1, Ep), scales, offsets=offsets, bits=bits,
            donate_input=not with_err,
        )
        full = transport.unpack_dequantize(
            w, scales, offsets=offsets, bits=bits, cols=Ep,
            donate_input=True,
        ).reshape(-1)[:E]
        return split(full), scales, (flat - full if with_err else None)

    pre_axes, wire_axes, pre, g = _wire_split(ctx.topology)
    # ---- level 1: exact f32 pre-combine, striping the payload ----------
    if pre > 1:
        S = -(-E // (pre * unit)) * unit
        if pre * S != E:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pre * S - E,), jnp.float32)]
            )
        stripe = lax.psum_scatter(
            flat, pre_axes, scatter_dimension=0, tiled=True
        )
        base_stripe = _flat_index(pre_axes) * S
    else:
        S = E
        stripe = flat
        base_stripe = jnp.zeros((), jnp.int32)
    # ---- one-pass quantize-pack of the stripe into g wire blocks -------
    B = -(-S // (g * unit)) * unit
    if g * B != S:
        stripe = jnp.concatenate(
            [stripe, jnp.zeros((g * B - S,), jnp.float32)]
        )
    s1 = _wire_scale(stripe, offsets, sizes, base_stripe, wire_axes, qmax)
    # the stripe buffer is only donated when EF is off — the error path
    # re-reads it after the call (the lint's alias-donation rule proves
    # this statically)
    w = transport.quantize_pack(
        stripe.reshape(g, B), s1, offsets=offsets, bits=bits,
        base=base_stripe, row_stride=B, donate_input=not with_err,
    )
    # ---- RS half: packed all_to_all; every row lands on the same block
    # window (base + t*B, row_stride=0), unpack + exact f32 fold --------
    recv = lax.all_to_all(
        w[:, None], wire_axes, split_axis=0, concat_axis=1, tiled=False
    )[0]
    block_base = base_stripe + _flat_index(wire_axes) * B
    blk = jnp.sum(
        transport.unpack_dequantize(
            recv, s1, offsets=offsets, bits=bits, cols=B,
            base=block_base, row_stride=0, donate_input=True,
        ),
        axis=0,
    )
    # ---- requantize the reduced fold at its measured bound; AG half ----
    s2 = _wire_scale(blk, offsets, sizes, block_base, wire_axes, qmax)
    w2 = transport.quantize_pack(
        blk.reshape(1, B), s2, offsets=offsets, bits=bits,
        base=block_base, row_stride=0, donate_input=not with_err,
    )
    gathered = lax.all_gather(w2[0], wire_axes, axis=0, tiled=False)
    stripe_sum = transport.unpack_dequantize(
        gathered, s2, offsets=offsets, bits=bits, cols=B,
        base=base_stripe, row_stride=B, donate_input=True,
    ).reshape(-1)[:S]
    # ---- level 1 inverse: rebuild the flat sum inside the node ---------
    if pre > 1:
        full = lax.all_gather(stripe_sum, pre_axes, axis=0, tiled=True)
    else:
        full = stripe_sum
    err = None
    if with_err:
        # this chip's share of the rounding error (see docstring): the
        # stripe it quantised on hop 1 and the block it requantised on
        # hop 2 (the block lies inside the stripe, so the two add).
        # Pure-jnp decode — no extra pallas_call sites under EF.
        vhat = transport.unpack_dequantize(
            w, s1, offsets=offsets, bits=bits, cols=B,
            base=base_stripe, row_stride=B, impl="xla",
        ).reshape(-1)
        e1 = (stripe - vhat)[:S]
        blkhat = transport.unpack_dequantize(
            w2, s2, offsets=offsets, bits=bits, cols=B,
            base=block_base, row_stride=0, impl="xla",
        )[0]
        # padded scratch: the last stripe's block window may run past
        # pre*S (block g*B > S); the overhang is all-zero padding
        P = (pre - 1) * S + g * B
        err = lax.dynamic_update_slice(
            jnp.zeros((P,), jnp.float32), e1, (base_stripe,)
        )
        cur = lax.dynamic_slice(err, (block_base,), (B,))
        err = lax.dynamic_update_slice(
            err, cur + (blk - blkhat), (block_base,)
        )[:E]
    return split(full[:E]), s1, err


def _compressed_allreduce(x, ctx: comm.CommContext, group):
    """Single-leaf quantised allreduce (float32 out; caller re-dtypes)."""
    outs, _, _ = _compressed_fused_allreduce([x.reshape(-1)], ctx, group)
    return outs[0].reshape(x.shape)


def _reduce_leaf(g, ctx: comm.CommContext, group):
    """Allreduce one payload with op/mean/dtype semantics in one place.

    Every payload — float, bf16, integer, fused flat bucket — funnels
    through here so the transport dtype, the mean division and the
    round-trip back to the original dtype cannot diverge between code
    paths.
    """
    dtype = g.dtype
    is_float = jnp.issubdtype(dtype, jnp.floating)
    if ctx.policy.compress_bits and is_float:
        red = _compressed_allreduce(g, ctx, group)
    else:
        red = _one_allreduce(g, ctx)
    if ctx.policy.mean and group > 1:
        if is_float:
            red = red / group
        else:
            red = jnp.round(red.astype(jnp.float32) / group)
    return red.astype(dtype)


# ---------------------------------------------------------------------------
# planner interface
# ---------------------------------------------------------------------------


def _leaf_specs(leaves, policy: comm.CommPolicy, group: int):
    def transport_itemsize(dt, fusible):
        if policy.compress_bits and fusible:
            # the *packed* wire width (0.5 B/elem at 4 bits, 1 B at 8):
            # the planner must budget the bytes the fused kernels move
            return transport.wire_itemsize(policy.compress_bits)
        return None

    return bucketing.leaf_specs_for(
        leaves, transport_itemsize_fn=transport_itemsize
    )


def _plan(leaves, policy: comm.CommPolicy, topology: comm.Topology):
    threshold = (
        policy.small_threshold_bytes
        if policy.small_threshold_bytes is None
        else int(policy.small_threshold_bytes)
    )
    return bucketing.plan_buckets(
        _leaf_specs(leaves, policy, topology.group),
        topology,
        algorithm=policy.algorithm,
        small_threshold_bytes=threshold,
        pipeline_chunks=policy.pipeline_chunks,
        bucket_bytes=policy.bucket_bytes,
        fuse=policy.fuse_small_buckets,
    )


def plan_for_tree(
    tree: Any,
    *,
    cfg: comm.CommPolicy,
    n: int | None = None,
    ppn: int | None = None,
    topology: comm.Topology | None = None,
) -> bucketing.BucketPlan:
    """Bucket plan for a gradient pytree (arrays or ShapeDtypeStructs).

    Host-side and trace-free: the trainer calls this once on the
    abstract gradient tree (``jax.eval_shape``) to own the per-bucket
    issue points, then hands the plan to the executor so the traced
    program executes exactly the schedule that was planned (and that the
    simulator prices).  Pass a :class:`comm.Topology` (preferred) or the
    legacy ``(n, ppn)`` pair.
    """
    if topology is None:
        topology = comm.Topology.of(int(n or 1), int(ppn or 1))
    leaves = jax.tree.flatten(tree)[0]
    return _plan(leaves, cfg, topology)


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------


def _bucket_ctx(ctx: comm.CommContext, bucket) -> comm.CommContext:
    """The per-bucket context: the planner's decision, pinned.

    ``small_threshold_bytes`` is cleared because the engine is already
    resolved — the trace-time dispatcher must not re-decide."""
    return comm.CommContext(
        ctx.topology,
        dataclasses.replace(
            ctx.policy,
            algorithm=bucket.algorithm,
            pipeline_chunks=bucket.chunks,
            small_threshold_bytes=None,
        ),
    )


def _execute_plan(leaves, plan, ctx: comm.CommContext, ef=None):
    """Issue every bucket's collective in plan (reverse-leaf) order.

    Buckets are data-independent; issuing them as separate collectives
    in backward-completion order is what lets XLA's latency-hiding
    scheduler overlap bucket ``b``'s transfer with the compute that
    produces bucket ``b+1`` — the in-SPMD form of bucket-level async.

    ``ef`` (optional) is the flat list of per-chip error-feedback
    residuals: compressed float buckets sync ``c = g + r`` and each
    chip's new residual is its exact share of the transport's rounding
    error (see :func:`_compressed_fused_allreduce` — measured at the
    compression points, not modelled per chip); every other leaf's
    residual passes through untouched.  Returns ``(out, new_ef)``.
    """
    group = ctx.topology.group
    bits = ctx.policy.compress_bits
    out = [None] * len(leaves)
    new_ef = None if ef is None else list(ef)
    for b, bucket in enumerate(plan.buckets):
        with scope(f"grad_sync.bucket{b}"):
            bctx = _bucket_ctx(ctx, bucket)
            idxs = bucket.leaves
            is_float = jnp.issubdtype(leaves[idxs[0]].dtype, jnp.floating)
            if bits and is_float:
                # fused + compressed: per-leaf scales (a shared scale would
                # zero out small-magnitude leaves), mean/dtype per segment
                parts = []
                for i in idxs:
                    p = leaves[i].reshape(-1).astype(jnp.float32)
                    if ef is not None:
                        p = p + ef[i].reshape(-1)
                    parts.append(p)
                segs, scales, err = _compressed_fused_allreduce(
                    parts, bctx, group, with_err=ef is not None
                )
                offs = _leaf_offsets(parts)
                for k, i in enumerate(idxs):
                    g = leaves[i]
                    if ef is not None:
                        new_ef[i] = err[
                            offs[k] : offs[k] + g.size
                        ].reshape(g.shape)
                    seg = segs[k]
                    if ctx.policy.mean and group > 1:
                        seg = seg / group
                    out[i] = seg.reshape(g.shape).astype(g.dtype)
                continue
            if len(idxs) == 1:
                i = idxs[0]
                out[i] = _reduce_leaf(leaves[i], bctx, group)
                continue
            flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
            red = _reduce_leaf(flat, bctx, group)
            off = 0
            for i in idxs:
                g = leaves[i]
                out[i] = red[off : off + g.size].reshape(g.shape)
                off += g.size
    return out, new_ef


def sync_with_context(
    grads: Any,
    ctx: comm.CommContext,
    *,
    plan: bucketing.BucketPlan | None = None,
    ef_state: Any | None = None,
) -> Any:
    """Bucket-scheduled allreduce sync under a :class:`comm.CommContext`
    (the canonical entry — :meth:`comm.CommContext.sync_grads`).

    ``plan`` (optional) is a precomputed :func:`plan_for_tree` result —
    the trainer's per-bucket issue points.  When omitted, the plan is
    solved here (host-side, cached per pytree signature x topology x
    policy).

    ``ef_state`` (optional) is the per-chip error-feedback residual tree
    (:func:`repro.optim.error_feedback.ef_init`) matching ``grads``
    leaf-for-leaf; when given, the call returns ``(synced, new_ef)``
    instead of just the synced tree.  Requires compressed transport —
    residuals of an exact sync would be identically zero.
    """
    ctx.topology.require_axes()
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return grads if ef_state is None else (grads, ef_state)
    ef_leaves = None
    if ef_state is not None:
        if not ctx.policy.compress_bits:
            raise ValueError(
                "ef_state given but compress_bits is None — error "
                "feedback only applies to quantised transport"
            )
        ef_leaves = jax.tree.flatten(ef_state)[0]
        if len(ef_leaves) != len(leaves):
            raise ValueError(
                f"error-feedback state has {len(ef_leaves)} leaves for "
                f"{len(leaves)} gradient leaves"
            )
    if plan is None:
        plan = _plan(leaves, ctx.policy, ctx.topology)
    else:
        sig = tuple(
            (int(np.prod(g.shape)) if g.shape else 1, np.dtype(g.dtype).name)
            for g in leaves
        )
        if sig != plan.signature:
            raise ValueError(
                "bucket plan does not match the gradient pytree "
                f"(plan for {plan.signature}, got {sig})"
            )
    out, new_ef = _execute_plan(leaves, plan, ctx, ef=ef_leaves)
    synced = jax.tree.unflatten(treedef, out)
    if ef_state is None:
        return synced
    return synced, jax.tree.unflatten(jax.tree.structure(ef_state), new_ef)


def sync_grads_local(
    grads: Any,
    *,
    cfg: comm.CommPolicy,
    inter_axes: tuple[str, ...],
    intra_axes: tuple[str, ...],
    plan: bucketing.BucketPlan | None = None,
) -> Any:
    """Synchronise a pytree of per-chip local gradients (inside shard_map).

    Axis-names entry point: builds a :class:`comm.Topology` from the
    named mesh axes (sizes resolved from the traced context) and a
    :class:`comm.CommContext` from ``cfg``, then runs
    :func:`sync_with_context`.
    """
    ctx = comm.CommContext(
        comm.Topology.from_axes(inter_axes, intra_axes), cfg
    )
    return sync_with_context(grads, ctx, plan=plan)


def _compressed_reduce_scatter(flat, scale, ctx: comm.CommContext):
    """RS half of the packed transport for one leaf: exact f32 intra
    ``psum_scatter``, one-pass quantize-pack of the stripe, packed
    inter-node ``all_to_all`` + unpack + f32 fold.  Returns the chip's
    f32 shard of the *sum*, ``ceil(ceil(e/ppn)/n)`` elements in the MLA
    stripe-block layout (bit-compatible with :func:`unshard_grads`).

    The scale is agreed globally *before* the scatter (one fused NAP-max
    collective for every leaf together), so all shards quantise on the
    same grid — there is nothing left to re-agree post-scatter, and no
    AG hop on this route means no second requantization either.
    """
    bits = ctx.policy.compress_bits
    topo = ctx.topology
    n, ppn = topo.n_nodes, topo.ppn
    scales = scale.reshape(1)
    offsets = (0,)
    e = int(flat.size)
    S = -(-e // ppn)
    if ppn > 1:
        if ppn * S != e:
            flat = jnp.concatenate([flat, jnp.zeros((ppn * S - e,), jnp.float32)])
        stripe = lax.psum_scatter(
            flat, topo.intra_axes, scatter_dimension=0, tiled=True
        )
        base = _flat_index(topo.intra_axes) * S
        s1 = scales * float(ppn)
    else:
        stripe = flat
        base = jnp.zeros((), jnp.int32)
        s1 = scales
    B = -(-S // n)
    if n <= 1:
        return stripe
    if n * B != S:
        stripe = jnp.concatenate([stripe, jnp.zeros((n * B - S,), jnp.float32)])
    w = transport.quantize_pack(
        stripe.reshape(n, B), s1, offsets=offsets, bits=bits,
        base=base, row_stride=B, donate_input=True,
    )
    recv = lax.all_to_all(
        w[:, None], topo.inter_axes, split_axis=0, concat_axis=1,
        tiled=False,
    )[0]
    block_base = base + _flat_index(topo.inter_axes) * B
    return jnp.sum(
        transport.unpack_dequantize(
            recv, s1, offsets=offsets, bits=bits, cols=B,
            base=block_base, row_stride=0, donate_input=True,
        ),
        axis=0,
    )


def sync_grads_sharded(
    grads: Any, *, ctx: comm.CommContext
) -> Any:
    """ZeRO-style sharded gradient sync (inside shard_map).

    Every leaf is *reduce-scattered* instead of allreduced: each chip
    keeps only its 1-D shard of the reduced (optionally averaged)
    gradient — the slice its optimizer partition owns — so per-chip
    inter-node bytes are half the allreduce round trip and the full
    gradient never materialises per chip.  Returns a pytree of 1-D
    shards (leaf ``i``'s shard has ``ceil(ceil(n_i/ppn)/n)`` elements,
    the MLA stripe-block layout); :func:`unshard_grads` inverts.

    With ``compress_bits`` set, float leaves ride the packed transport's
    RS half (:func:`_compressed_reduce_scatter`): per-leaf scales are
    agreed in ONE fused NAP-max collective before the scatter (so every
    shard quantises on the same grid), then each leaf moves as wire
    bytes over the slow domain — the same ``bits/8`` per-chip inter-node
    byte ratio as the allreduce route, at half the hops.  Integer leaves
    stay exact.
    """
    ctx.topology.require_axes()
    group = ctx.topology.group
    leaves, treedef = jax.tree.flatten(grads)
    bits = ctx.policy.compress_bits
    qmax = float(2 ** (bits - 1) - 1) if bits else None
    compressed = [
        i for i, g in enumerate(leaves)
        if bits and jnp.issubdtype(g.dtype, jnp.floating)
    ]
    scales = {}
    if compressed and group > 1:
        # ONE fused scale agreement for every compressed leaf together
        agreed = _agreed_absmax(
            [leaves[i].reshape(-1) for i in compressed], ctx
        )
        scales = {
            i: jnp.maximum(agreed[k] / qmax, 1e-30)
            for k, i in enumerate(compressed)
        }
    out = []
    for i, g in enumerate(leaves):
        dtype = g.dtype
        is_float = jnp.issubdtype(dtype, jnp.floating)
        if i in scales:
            red = _compressed_reduce_scatter(
                g.reshape(-1).astype(jnp.float32), scales[i], ctx
            )
        else:
            red = ctx.reduce_scatter(g.reshape(-1), op="sum")
        if ctx.policy.mean and group > 1:
            if is_float:
                red = red / group
            else:
                red = jnp.round(red.astype(jnp.float32) / group)
        out.append(red.astype(dtype))
    return jax.tree.unflatten(treedef, out)


def unshard_grads(shards: Any, like: Any, *, ctx: comm.CommContext) -> Any:
    """Allgather a :func:`sync_grads_sharded` result back to full leaves.

    ``like`` is a pytree of arrays or ShapeDtypeStructs giving the
    original leaf shapes (the padding stripped per leaf).
    """
    shard_leaves, treedef = jax.tree.flatten(shards)
    like_leaves = jax.tree.flatten(like)[0]
    out = []
    for s, g in zip(shard_leaves, like_leaves):
        elems = int(np.prod(g.shape)) if g.shape else 1
        full = ctx.allgather(s, elems=elems)
        out.append(full.reshape(g.shape).astype(g.dtype))
    return jax.tree.unflatten(treedef, out)


def make_grad_sync(
    cfg: comm.CommPolicy,
    mesh,
    *,
    data_axes: tuple[str, ...],
    grad_specs: Any,
):
    """Standalone grad-sync callable over global arrays.

    ``grad_specs`` is a pytree of PartitionSpecs matching the gradients;
    leaves must not be sharded along ``data_axes`` dims other than the
    stacked per-replica leading dim used in DP.
    """
    from ..launch.mesh import POD_AXIS

    inter = tuple(a for a in data_axes if a == POD_AXIS)
    intra = tuple(a for a in data_axes if a != POD_AXIS)

    def _local(grads):
        return sync_grads_local(
            grads, cfg=cfg, inter_axes=inter, intra_axes=intra
        )

    return jax.shard_map(
        _local, mesh=mesh, in_specs=(grad_specs,), out_specs=grad_specs,
        check_vma=transport.vma_checkable(),
    )
