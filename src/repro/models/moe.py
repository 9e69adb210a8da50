"""Mixture-of-Experts FFN with explicit expert parallelism.

Routed experts are sharded over the ``model`` mesh axis (EP).  Dispatch is
the production-style two-hop:

  1. tokens are bucketed by *destination EP rank* (capacity-bounded,
     deterministic cumsum positions) and exchanged with one
     ``lax.all_to_all`` over the model axis;
  2. received tokens are bucketed per *local expert*, run through a
     batched (E_local, C, D) x (E_local, D, F) GLU, and returned by the
     reverse ``all_to_all``; gathers (never scatters) restore token order.

The EP hop runs inside ``jax.shard_map`` so the collective schedule is
explicit — the same design decision as the paper's NAP collective (static
schedules beat compiler guessing); everything else stays in auto-sharded
jit.  Without a mesh (CPU smoke tests) the same local routine handles all
experts directly.

DeepSeek-style shared experts ride the dense path; a load-balance aux
loss (Switch-style) is returned for the trainer.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .layers import _ACTS, dense, init_dense, init_glu_mlp, glu_mlp

__all__ = ["init_moe", "moe_apply"]


def init_moe(key, cfg, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    params = {
        "w_router": init_dense(ks[0], d, m.num_experts, jnp.float32),
        "we_gate": _init_experts(ks[1], m.num_experts, d, m.d_expert, dtype),
        "we_up": _init_experts(ks[2], m.num_experts, d, m.d_expert, dtype),
        "we_down": _init_experts(ks[3], m.num_experts, m.d_expert, d, dtype),
    }
    if m.num_shared_experts:
        params["shared"] = init_glu_mlp(
            ks[4], d, m.num_shared_experts * m.d_expert, dtype
        )
    return params


def _init_experts(key, e, d_in, d_out, dtype):
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (e, d_in, d_out)) * scale).astype(dtype)


def _capacity(tokens: int, k: int, buckets: int, factor: float) -> int:
    cap = int(math.ceil(tokens * k / buckets * factor))
    return max(8, ((cap + 7) // 8) * 8)  # pad to 8 for TPU-friendly tiles


def _bucket_positions(dest: jax.Array, n_buckets: int, cap: int):
    """Deterministic position of each item inside its destination bucket.

    dest: (N,) int32 bucket ids. Returns (pos (N,), keep (N,) bool).
    """
    onehot = jax.nn.one_hot(dest, n_buckets, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1  # (N, buckets)
    pos = jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]
    return pos, pos < cap


def _expert_ffn(we_gate, we_up, we_down, x, act: str):
    """Batched per-expert GLU: x (E, C, D) -> (E, C, D)."""
    h = _ACTS[act](jnp.einsum("ecd,edf->ecf", x, we_gate))
    h = h * jnp.einsum("ecd,edf->ecf", x, we_up)
    return jnp.einsum("ecf,efd->ecd", h, we_down)


def _route_local(
    x_flat, top_idx, top_gate, we_gate, we_up, we_down, *, cap_factor, act
):
    """All experts resident locally: bucket per expert, batched GLU, gather.

    x_flat: (T, D); top_idx/top_gate: (T, K).
    """
    T, D = x_flat.shape
    E = we_gate.shape[0]
    K = top_idx.shape[1]
    flat_dest = top_idx.reshape(-1)  # (T*K,)
    cap = _capacity(T, K, E, cap_factor)
    pos, keep = _bucket_positions(flat_dest, E, cap)
    slot = jnp.where(keep, flat_dest * cap + pos, E * cap)  # overflow row
    buf = jnp.zeros((E * cap + 1, D), x_flat.dtype)
    src = jnp.repeat(x_flat, K, axis=0)
    buf = buf.at[slot].set(src)  # unique slots: set, not add
    out = _expert_ffn(
        we_gate, we_up, we_down, buf[:-1].reshape(E, cap, D), act
    )
    y = out.reshape(E * cap, D)
    y = jnp.concatenate([y, jnp.zeros((1, D), y.dtype)])  # dropped -> 0
    gathered = y[slot] * top_gate.reshape(-1)[:, None].astype(y.dtype)
    return gathered.reshape(T, K, D).sum(axis=1)


def _route_ep(
    x_flat,
    top_idx,
    top_gate,
    we_gate,
    we_up,
    we_down,
    *,
    tp_axis,
    fsdp_axes,
    partial_axes=(),
    cap_factor,
    act,
):
    """Two-hop EP dispatch inside shard_map. x_flat: (T_local, D).

    ``fsdp_axes``: training layout — expert reduce dims FSDP-sharded,
    gathered here before the batched GLU.  ``partial_axes``: serving
    layout — the expert F dim is sharded over the data axes instead, so
    the down-projection yields partial sums reduced with one activation-
    sized psum (no weight gathers; the 2D-serve optimization).
    """
    ranks = lax.axis_size(tp_axis)
    if fsdp_axes:
        # FSDP shards the *reduce* dim: axis 1 (D) for gate/up, axis 2 (D)
        # for down (its layout is (E, F, D)).
        we_gate = lax.all_gather(we_gate, fsdp_axes, axis=1, tiled=True)
        we_up = lax.all_gather(we_up, fsdp_axes, axis=1, tiled=True)
        we_down = lax.all_gather(we_down, fsdp_axes, axis=2, tiled=True)
    e_local = we_gate.shape[0]
    T, D = x_flat.shape
    K = top_idx.shape[1]

    # hop 1: bucket by destination rank
    flat_dest_rank = (top_idx // e_local).reshape(-1)
    cap_s = _capacity(T, K, ranks, cap_factor)
    pos1, keep1 = _bucket_positions(flat_dest_rank, ranks, cap_s)
    slot1 = jnp.where(keep1, flat_dest_rank * cap_s + pos1, ranks * cap_s)
    send = jnp.zeros((ranks * cap_s + 1, D), x_flat.dtype)
    send = send.at[slot1].set(jnp.repeat(x_flat, K, axis=0))
    send_eid = jnp.full((ranks * cap_s + 1,), -1, jnp.int32)
    send_eid = send_eid.at[slot1].set(
        (top_idx % e_local).reshape(-1).astype(jnp.int32)
    )
    recv = lax.all_to_all(
        send[:-1].reshape(ranks, cap_s, D), tp_axis, 0, 0, tiled=False
    ).reshape(ranks * cap_s, D)
    recv_eid = lax.all_to_all(
        send_eid[:-1].reshape(ranks, cap_s, 1), tp_axis, 0, 0, tiled=False
    ).reshape(ranks * cap_s)

    # hop 2: bucket received tokens per local expert.  With a single
    # local expert every received token lands on it by construction, so
    # no second capacity factor applies (a 1.25x waste of expert flops
    # otherwise — measured on jamba: ~20% of total train compute).
    N = ranks * cap_s
    cap_e = _capacity(N, 1, e_local, cap_factor if e_local > 1 else 1.0)
    valid = recv_eid >= 0
    dest2 = jnp.where(valid, recv_eid, 0)
    pos2, keep2 = _bucket_positions(dest2, e_local, cap_e)
    keep2 &= valid
    slot2 = jnp.where(keep2, dest2 * cap_e + pos2, e_local * cap_e)
    buf = jnp.zeros((e_local * cap_e + 1, D), x_flat.dtype)
    buf = buf.at[slot2].set(recv)
    out = _expert_ffn(
        we_gate, we_up, we_down, buf[:-1].reshape(e_local, cap_e, D), act
    )
    if partial_axes:  # serve2d: F was sharded -> partial sums over data
        out = lax.psum(out, partial_axes)
    y = jnp.concatenate(
        [out.reshape(e_local * cap_e, D), jnp.zeros((1, D), out.dtype)]
    )
    back = y[slot2]  # (N, D): dropped -> 0, restored to recv order

    # reverse hop 1
    ret = lax.all_to_all(
        back.reshape(ranks, cap_s, D), tp_axis, 0, 0, tiled=False
    ).reshape(ranks * cap_s, D)
    ret = jnp.concatenate([ret, jnp.zeros((1, D), ret.dtype)])
    gathered = ret[slot1] * top_gate.reshape(-1)[:, None].astype(ret.dtype)
    return gathered.reshape(T, K, D).sum(axis=1)


def moe_apply(params, x: jax.Array, *, cfg, policy):
    """MoE FFN: x (B, S, D) -> (y (B, S, D), aux_loss scalar)."""
    m = cfg.moe
    B, S, D = x.shape
    logits = dense(x, params["w_router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_gate, top_idx = lax.top_k(probs, m.top_k)
    top_gate = top_gate / jnp.clip(
        top_gate.sum(-1, keepdims=True), 1e-9
    )  # renormalise over selected

    # Switch-style load-balance loss
    density = jnp.mean(
        jax.nn.one_hot(top_idx, m.num_experts, dtype=jnp.float32), axis=(0, 1, 2)
    )
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = m.router_aux_weight * m.num_experts * jnp.sum(density * mean_prob)

    use_ep = (
        policy.mesh is not None
        and policy.tp_axis is not None
        and m.num_experts % policy.tp_size == 0
        and policy.tp_size > 1
        # shard_map needs the token dim divisible by the dp axes (decode
        # with batch < dp falls back to the local route — cheap there)
        and (B * S) % max(policy.dp_size, 1) == 0
    )
    if use_ep:
        gate_spec = policy.spec_for("we_gate", params["we_gate"].shape)
        specs_in = (
            P(policy.dp, None),                    # x_flat
            P(policy.dp, None),                    # top_idx
            P(policy.dp, None),                    # top_gate
            gate_spec,
            policy.spec_for("we_up", params["we_up"].shape),
            policy.spec_for("we_down", params["we_down"].shape),
        )
        def _axes_of(entry):
            if entry is None:
                return ()
            return entry if isinstance(entry, tuple) else (entry,)

        fsdp_axes = _axes_of(gate_spec[1] if len(gate_spec) > 1 else None)
        down_spec = policy.spec_for("we_down", params["we_down"].shape)
        partial_axes = (
            _axes_of(down_spec[1] if len(down_spec) > 1 else None)
            if policy.mode == "serve2d"
            else ()
        )
        routed = jax.shard_map(
            partial(
                _route_ep,
                tp_axis=policy.tp_axis,
                fsdp_axes=fsdp_axes,
                partial_axes=partial_axes,
                cap_factor=m.capacity_factor,
                act=cfg.act,
            ),
            mesh=policy.mesh,
            in_specs=specs_in,
            out_specs=P(policy.dp, None),
            check_vma=False,
        )(
            x.reshape(B * S, D),
            top_idx.reshape(B * S, m.top_k),
            top_gate.reshape(B * S, m.top_k),
            params["we_gate"],
            params["we_up"],
            params["we_down"],
        )
    else:
        routed = _route_local(
            x.reshape(B * S, D),
            top_idx.reshape(B * S, m.top_k),
            top_gate.reshape(B * S, m.top_k),
            params["we_gate"],
            params["we_up"],
            params["we_down"],
            cap_factor=m.capacity_factor,
            act=cfg.act,
        )
    y = routed.reshape(B, S, D)
    if "shared" in params:
        y = y + glu_mlp(params["shared"], x, cfg.act)
    return y.astype(x.dtype), aux
