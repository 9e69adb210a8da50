"""`CommContext`-routed tensor-parallel decode path for the serving spine.

Traced building blocks shared by :class:`repro.serve.engine.ServeEngine`,
the refactored :mod:`repro.launch.serve` wrappers, and the
``python -m repro.analysis --spmd`` sweep.  Three decode-time
collectives, each routed where the cost model says it belongs:

* **per-token logits allreduce** — the latency-regime workload the paper
  optimises: the partial head products are ``group * slots * V`` floats
  (tens of KB at serving vocab shards), far below
  ``Topology.crossover_bytes()`` on multi-node grids, so auto dispatch
  lands on NAP (``log_ppn(n)`` inter-node steps) per token;
* **hidden-state gather** — the slot-sharded final hidden states are
  rebuilt on every chip through ``ctx.allgather`` pinned to ``mla_ag``
  on multi-node grids (the striped KV-cache/activation gather), whose
  lane-major payload layout this module's block indexing mirrors;
* **EOS early-exit min-reduce** — pinned to the native ``psum`` engine:
  a value steering a ``while_loop`` predicate must be *provably*
  rank-uniform, and only a whole-group reduction primitive clears rank
  variance in the spmd lint's dataflow lattice (the PR-8 lint-clean
  form).

The tensor-parallel head splits the ``D`` contraction, not the vocab:
every chip sees the full gathered hidden block, contracts its own
``D/group`` column slice against the same slice of the head matrix, and
the sum over chips is recovered by the logits allreduce.  Contraction
(not vocab) sharding keeps the ``argmax`` local — no second collective
to find the winning token — and makes the allreduce payload exactly the
per-token logits, the paper's canonical small-message workload.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core import comm
from ..models.layers import softcap

__all__ = [
    "payload_block_index",
    "group_all_min",
    "make_tp_head",
    "make_decode_slice",
    "make_decode_loop",
    "greedy_step",
    "slot_axes",
    "stack_slot_cache",
]


def _flat_axis_index(axes: tuple[str, ...]) -> jax.Array:
    """Row-major flattened ``lax.axis_index`` over named ``axes`` (0 if
    none)."""
    idx = jnp.zeros((), jnp.int32)
    for ax in axes:
        idx = idx * lax.axis_size(ax) + lax.axis_index(ax)
    return idx


def payload_block_index(topology: comm.Topology) -> jax.Array:
    """This chip's block index in the striped allgather payload.

    ``mla_allgather`` rebuilds the flat payload lane-major: the intra
    all_gather stacks per-lane stripes, each stripe the inter all_gather
    of that lane's node shards — so chip ``(node j, lane r)`` owns block
    ``r * n_nodes + j``.  Degenerate grids (``n == 1`` or ``ppn == 1``)
    collapse to the chip-order layout of the flat fallback engine, so
    this single formula is layout-correct for whichever allgather engine
    :meth:`CommContext.dispatch` selects on those grids.  Traced (needs
    bound axes).
    """
    j = _flat_axis_index(topology.inter_axes)
    r = _flat_axis_index(topology.intra_axes)
    return r * topology.n_nodes + j


def group_all_min(ctx: comm.CommContext | None, flag: jax.Array) -> jax.Array:
    """Group-agreed "everyone done" flag for while-predicate use.

    Pinned to the native ``psum`` engine, not the latency dispatch: a
    value that steers control flow must be *provably* uniform, and only
    a whole-group reduction primitive clears rank variance in the spmd
    lint's dataflow lattice.  NAP's masked-permute output is uniform
    algorithmically but not provably so — the uniformity rule
    (correctly) rejects it as a while predicate.
    """
    if ctx is None or not (
        ctx.topology.inter_axes or ctx.topology.intra_axes
    ):
        return flag
    return ctx.allreduce(flag, op="min", algorithm="psum")


def make_tp_head(model, ctx: comm.CommContext | None):
    """Build the tensor-parallel greedy head:
    ``(params, hidden (b, 1, D)) -> next tokens (b, 1) int32``.

    With a bound multi-chip ``ctx`` the input is this chip's slot shard;
    the returned tokens are the same shard's next tokens.  Without one
    (or on a 1-chip topology) it degenerates to the local head einsum —
    same contraction, ``preferred_element_type=f32``, softcap after.
    """
    cfg = model.cfg
    use_comm = ctx is not None and ctx.topology.group > 1 and bool(
        ctx.topology.inter_axes or ctx.topology.intra_axes
    )

    if not use_comm:

        def local_head(params, hidden):
            w = model.head_weights(params)
            logits = jnp.einsum(
                "bsd,dv->bsv", hidden, w.astype(hidden.dtype),
                preferred_element_type=jnp.float32,
            )
            logits = softcap(logits, cfg.final_logit_softcap)
            logits = model.policy.act(logits, kind="logits")
            return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[
                :, None
            ]

        return local_head

    topo = ctx.topology
    group = topo.group
    D = cfg.d_model
    # pad the contraction so every chip owns an equal column slice; the
    # zero columns contribute nothing to the einsum
    d_cols = -(-D // group)
    Dp = d_cols * group
    # the striped gather is the point on multi-node grids; on flat grids
    # auto dispatch resolves to the (layout-compatible) fallback
    ag_algorithm = "mla_ag" if topo.has_slow_domain else None

    def tp_head(params, hidden):
        b, s, _ = hidden.shape
        assert s == 1, "decode head expects single-position hidden states"
        h = hidden.reshape(b, D).astype(jnp.float32)
        if Dp != D:
            h = jnp.pad(h, ((0, 0), (0, Dp - D)))
        # rebuild every chip's slot rows on all chips (lane-major blocks)
        full = ctx.allgather(
            h.reshape(-1), elems=group * b * Dp, algorithm=ag_algorithm
        ).reshape(group * b, Dp)
        bi = payload_block_index(topo)
        w = model.head_weights(params).astype(jnp.float32)
        if Dp != D:
            w = jnp.pad(w, ((0, Dp - D), (0, 0)))
        h_slice = lax.dynamic_slice_in_dim(full, bi * d_cols, d_cols, axis=1)
        w_slice = lax.dynamic_slice_in_dim(w, bi * d_cols, d_cols, axis=0)
        partial = jnp.einsum(
            "bd,dv->bv", h_slice, w_slice,
            preferred_element_type=jnp.float32,
        )
        # the latency-regime allreduce: tiny per-token payload, auto
        # dispatch -> NAP on multi-node grids (below crossover_bytes)
        logits = ctx.allreduce(partial, op="sum")
        logits = softcap(logits, cfg.final_logit_softcap)
        tok_all = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # every chip computed all rows; keep only this chip's block
        return lax.dynamic_slice_in_dim(tok_all, bi * b, b, axis=0)[:, None]

    return tp_head


def greedy_step(model, ctx: comm.CommContext | None = None):
    """One-token cached greedy decode:
    ``(params, cache, tokens) -> (next tokens (B, 1), cache)``.

    The shared decode step: :func:`repro.launch.steps.make_serve_step`
    and the slot engine both run exactly this.  With ``ctx`` the head is
    the tensor-parallel path above; without, the model's own head.
    """
    head = make_tp_head(model, ctx)

    def step(params, cache, tokens):
        hidden, new_cache = model.decode_hidden(params, cache, tokens)
        return head(params, hidden), new_cache

    return step


# ---------------------------------------------------------------------------
# slot-stacked decode slice (the engine's jitted inner loop)
# ---------------------------------------------------------------------------


def slot_axes(cache) -> dict:
    """Where the slot axis sits in each top-level entry of a slot-stacked
    cache (a pytree prefix, usable as vmap axes).

    The layer-stacked ``stack`` leaves keep their layer axis first and
    take the slot axis second: that is the order the layer scan walks,
    so the slot ``vmap`` of the scan costs no relayout.  Slot-major
    stack leaves would be copied into layer-major order and back on
    every call of the slice.  The other entries (``index``,
    ``enc_out``) are slot-major.
    """
    return {k: 1 if k == "stack" else 0 for k in cache}


def stack_slot_cache(b1_cache, slots: int):
    """Slot-stacked cache holding ``slots`` copies of a B=1 cache, laid
    out as :func:`slot_axes` says."""

    def stack(x, ax):
        x = jnp.expand_dims(x, ax)
        return jnp.broadcast_to(x, x.shape[:ax] + (slots,) + x.shape[ax + 1:])

    return {
        k: jax.tree.map(lambda x, ax=ax: stack(x, ax), b1_cache[k])
        for k, ax in slot_axes(b1_cache).items()
    }


def _vmapped_decode_hidden(model, cache_axes: dict):
    """``decode_hidden`` over a slot-stacked cache (slot axes
    ``cache_axes``, inner B=1 caches); tokens are ``(slots, 1)``.
    Returns ``(hidden (slots, 1, D), new stacked cache)``."""

    def one(params, cache, tok):
        return model.decode_hidden(params, cache, tok[None])  # B=1

    return jax.vmap(
        one, in_axes=(None, cache_axes, 0), out_axes=(0, cache_axes)
    )


def make_decode_slice(
    model,
    ctx: comm.CommContext | None,
    *,
    slice_len: int,
    eos_id: int | None = None,
):
    """Build the jitted decode slice
    ``(params, cache, tok, active) -> (out, tok', cache', steps)``.

    ``cache`` is slot-stacked (inner B=1 caches, slot axes as
    :func:`slot_axes` says), ``tok`` is
    ``(slots, 1)`` int32 — the next token to feed — and ``active`` is
    ``(slots,)`` bool slot occupancy.  The slice records up to
    ``slice_len`` tokens per slot into ``out (slots, slice_len)``
    (column ``t`` is the token *emitted* at step ``t``; trailing columns
    are zero after an early exit) and returns the carry token for the
    next slice plus ``steps``, the number of decode steps actually
    executed (rank-uniform: the early exit is group-agreed).  Inactive slots still compute (their rows are garbage
    the scheduler drops) but their done flags are forced so they never
    hold up the EOS early exit, which is min-reduced through the native
    ``psum`` engine so the ``while_loop`` predicate is rank-uniform.

    Membership changes (admission, eviction, slot reuse) happen *between*
    slices — the continuous-batching boundary — by scattering fresh B=1
    prefill caches into slot rows; this function never resizes.
    """
    head = make_tp_head(model, ctx)

    def slice_fn(params, cache, tok, active):
        slots = tok.shape[0]
        decode_hidden = _vmapped_decode_hidden(model, slot_axes(cache))
        out0 = jnp.zeros((slots, slice_len), jnp.int32)
        done0 = ~active
        stop0 = jnp.zeros((), jnp.float32)

        def cond(carry):
            t, _tok, _cache, _out, _done, stop = carry
            return (t < slice_len) & (stop < 0.5)

        def body(carry):
            t, tok, cache, out, done, stop = carry
            out = lax.dynamic_update_slice(out, tok, (0, t))
            hidden, cache = decode_hidden(params, cache, tok)
            nxt = head(params, hidden.reshape(slots, 1, -1))
            if eos_id is not None:
                done = done | (tok[:, 0] == eos_id)
                nxt = jnp.where(done[:, None], eos_id, nxt)
            stop = group_all_min(
                ctx, jnp.all(done).astype(jnp.float32)
            )
            return t + 1, nxt, cache, out, done, stop

        carry = (jnp.zeros((), jnp.int32), tok, cache, out0, done0, stop0)
        t, tok, cache, out, _, _ = lax.while_loop(cond, body, carry)
        return out, tok, cache, t

    return slice_fn


# ---------------------------------------------------------------------------
# whole-batch greedy decode loop (the launch/serve.py wrapper's core)
# ---------------------------------------------------------------------------


def make_decode_loop(model, ctx: comm.CommContext | None = None, *,
                     gen_len: int, eos_id: int | None = None):
    """Build the jitted greedy decode loop ``(params, cache, tok) ->
    (B, gen_len) tokens`` (the fixed-batch serve path).

    ``tok`` is the (B, 1) first generated token (argmax of the last
    prefill logits).  With ``eos_id`` set the loop exits early once
    every sequence has emitted it; with a ``ctx`` whose topology has
    bound axes, "every sequence" means *across the whole serving
    group*: the local all-done flag is min-reduced through
    ``ctx.allreduce`` pinned to the native ``psum`` engine so the
    ``while_loop`` predicate is uniform on every rank — the lint-clean
    form of distributed early exit.
    """

    def decode(params, cache, tok):
        B = tok.shape[0]
        out0 = jnp.zeros((B, gen_len), jnp.int32)
        done0 = jnp.zeros((B,), bool)
        # group-agreed stop flag: starts "not done", updated from the
        # min-reduced all-done flag so every rank sees the same value
        stop0 = jnp.zeros((), jnp.float32)

        def cond(carry):
            t, _tok, _cache, _out, _done, stop = carry
            return (t < gen_len) & (stop < 0.5)

        def body(carry):
            t, tok, cache, out, done, stop = carry
            out = lax.dynamic_update_slice(out, tok, (0, t))
            logits, cache = model.decode_step(params, cache, tok)
            nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
            if eos_id is not None:
                done = done | (tok[:, 0] == eos_id)
                nxt = jnp.where(done[:, None], eos_id, nxt)
                stop = group_all_min(
                    ctx, jnp.all(done).astype(jnp.float32)
                )
            return t + 1, nxt, cache, out, done, stop

        carry = (jnp.zeros((), jnp.int32), tok, cache, out0, done0, stop0)
        _, _, _, out, _, _ = lax.while_loop(cond, body, carry)
        return out

    return decode
