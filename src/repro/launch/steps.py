"""Step builders: train_step / prefill_step / serve_step per (arch, shape).

These are the functions the dry-run lowers and the drivers jit:

* ``train_step``  — microbatched grad-accumulation loss/grad/AdamW update;
  gradient sync is XLA-propagated (FSDP reduce-scatter) by default, with
  the paper's NAP collective handling the latency-bound scalar sync
  (loss / grad-norm metrics) in the explicit path.
* ``prefill_step`` — forward over the full prompt; returns the final-
  position logits window (full (B, S, V) logits never materialise).
* ``serve_step``  — one-token cached decode (greedy next token).

``input_specs(...)`` produces ShapeDtypeStruct stand-ins (+ shardings)
for every model input of an (arch x shape x mesh) cell — the dry-run
lowers against these, so no host memory is ever allocated for the 72B/
398B configs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs import SHAPES, get_config
from ..models import ShardingPolicy, build_model
from ..optim import adamw_init, adamw_update, make_schedule
from ..configs.base import OptimizerConfig, ShapeConfig
from ..runtime.tracing import scope
from .mesh import dp_axes as mesh_dp_axes

__all__ = [
    "make_policy",
    "make_train_step",
    "make_dp_train_step",
    "init_dp_train_state",
    "make_prefill_step",
    "make_serve_step",
    "input_specs",
    "state_specs",
    "microbatch_split",
]


def make_policy(
    cfg,
    mesh: Mesh | None,
    *,
    seq_parallel: bool = False,
    mode: str = "train",
) -> ShardingPolicy:
    if mesh is None:
        return ShardingPolicy()
    dp = mesh_dp_axes(mesh)
    return ShardingPolicy(
        mesh=mesh,
        dp_axes=dp if mode != "serve2d" else (),
        tp_axis="model" if "model" in mesh.axis_names else None,
        fsdp_axes=dp,
        seq_parallel=seq_parallel,
        mode=mode,
    )


def microbatch_split(cfg, shape: ShapeConfig, mesh: Mesh | None) -> int:
    """Number of grad-accumulation microbatches for a train cell.

    Sized so the scan-over-layers residual carry (num_super x B_m x S x D
    bf16 per chip) stays ~<= 6 GB; must divide the per-chip batch.
    """
    if shape.kind != "train":
        return 1
    dp = 1
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp = int(np.prod([sizes[a] for a in mesh_dp_axes(mesh)]))
    b_local = max(1, shape.global_batch // dp)
    carry_per_sample = cfg.num_super_layers * shape.seq_len * cfg.d_model * 2
    b_m = max(1, int(6e9 // max(carry_per_sample, 1)))
    b_m = min(b_m, b_local)
    while b_local % b_m:
        b_m -= 1
    return b_local // b_m


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------


def make_train_step(
    model,
    opt_cfg: OptimizerConfig,
    *,
    n_micro: int = 1,
    grad_shardings=None,
):
    """grad_shardings: optional pytree of NamedShardings (same structure
    as params).  Annotating the grad-accumulation carry keeps gradients
    in the parameters' sharded layout — without it XLA resolves the
    unannotated zeros carry to replicated and synchronises every
    microbatch with full all-reduces instead of reduce-scatters
    (measured: 2.9 TB -> reduce-scatter-sized traffic on qwen2-72b)."""
    sched = make_schedule(opt_cfg)

    def _constrain(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(
            jax.lax.with_sharding_constraint, tree, grad_shardings
        )

    def train_step(state, batch):
        params, opt = state["params"], state["opt"]

        def loss_fn(p, mb):
            return model.loss(p, mb)

        with scope("train.fwd_bwd"):
            if n_micro == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, batch)
                grads = _constrain(grads)
            else:
                def micro(b):
                    return jax.tree.map(
                        lambda x: x.reshape((n_micro, -1) + x.shape[1:]), b
                    )

                mbs = micro(batch)

                def body(carry, mb):
                    acc, lsum = carry
                    (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                        params, mb
                    )
                    acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32), acc, g
                    )
                    return (_constrain(acc), lsum + l), None

                zeros = _constrain(
                    jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params
                    )
                )
                (grads, lsum), _ = jax.lax.scan(
                    body, (zeros, jnp.zeros((), jnp.float32)), mbs
                )
                grads = jax.tree.map(lambda g: g / n_micro, grads)
                loss = lsum / n_micro
                metrics = {"loss": loss}
        with scope("train.optimizer"):
            lr = sched(opt.step)
            new_params, new_opt, om = adamw_update(
                grads,
                opt,
                params,
                lr=lr,
                betas=opt_cfg.betas,
                eps=opt_cfg.eps,
                weight_decay=opt_cfg.weight_decay,
                grad_clip=opt_cfg.grad_clip,
            )
        out_metrics = {"loss": loss, "lr": lr, **om}
        return {"params": new_params, "opt": new_opt}, out_metrics

    return train_step


def make_dp_train_step(cfg, opt_cfg: OptimizerConfig, mesh, sync_cfg):
    """Pure data-parallel train step with *explicit* paper collectives.

    Parameters are replicated; each chip computes gradients on its batch
    shard; gradient buckets and the loss scalar are synchronised through
    a :class:`repro.core.comm.CommContext` built from the mesh topology
    and the configured policy (``nap`` / ``rd`` / ``smp`` / ``psum`` /
    ``auto`` …) inside one ``shard_map`` — the paper's technique
    integrated end-to-end in training.  Numerically equivalent to the
    ``psum`` baseline (asserted in tests).

    With ``sync_cfg.error_feedback`` the train state carries the
    per-chip compression residuals under ``"ef"`` — build them with
    :func:`repro.optim.error_feedback.ef_init(params, group=topo.group)
    <repro.optim.error_feedback.ef_init>`: every leaf has a leading
    group axis laid out over the mesh (residuals are chip-local state
    and must never be stored replicated).  Each step syncs ``g + r``
    through the quantised transport and stores back what the wire
    dropped.  :func:`init_dp_train_state` builds the whole state already
    laid out on the mesh.
    """
    from ..core import comm, grad_sync
    from ..models import ShardingPolicy
    from .mesh import mesh_topology

    model = build_model(cfg, ShardingPolicy())  # all compute chip-local
    sched = make_schedule(opt_cfg)
    topo = mesh_topology(mesh)
    ctx = comm.CommContext(topo, sync_cfg)
    group = topo.group

    # the trainer owns the per-bucket issue points: the bucket schedule is
    # planned once from the abstract gradient tree (same structure/dtypes
    # as the parameters) and pinned into every traced step, so the issue
    # order the scheduler decided is exactly what the SPMD program runs
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    bucket_plan = grad_sync.plan_for_tree(
        params_sds, cfg=sync_cfg, topology=topo
    )

    use_ef = bool(getattr(sync_cfg, "error_feedback", False))

    def local_step(state, batch):
        params, opt = state["params"], state["opt"]
        with scope("train.fwd_bwd"):
            (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
                params, batch
            )
        with scope("train.grad_sync"):
            if use_ef:
                ef = jax.tree.map(lambda e: e[0], state["ef"])
                grads, new_ef = ctx.sync_grads(
                    grads, plan=bucket_plan, ef_state=ef
                )
            else:
                grads = ctx.sync_grads(grads, plan=bucket_plan)
        # the paper's canonical workload: single-scalar latency-bound
        # allreduce (loss mean) through the same algorithm
        with scope("train.loss_sync"):
            if topo.inter_axes:
                loss = ctx.allreduce(
                    loss,
                    algorithm=sync_cfg.algorithm
                    if sync_cfg.algorithm != "auto" else "nap",
                ) / group
            else:
                loss = jax.lax.pmean(loss, topo.intra_axes)
        with scope("train.optimizer"):
            lr = sched(opt.step)
            new_params, new_opt, om = adamw_update(
                grads, opt, params,
                lr=lr, betas=opt_cfg.betas, eps=opt_cfg.eps,
                weight_decay=opt_cfg.weight_decay,
                grad_clip=opt_cfg.grad_clip,
            )
        new_state = {"params": new_params, "opt": new_opt}
        if use_ef:
            # residuals are per-chip: keep the leading group axis
            new_state["ef"] = jax.tree.map(lambda e: e[None], new_ef)
        return new_state, {"loss": loss, "lr": lr, **om}

    state_spec = {"params": P(), "opt": P()}
    if use_ef:
        state_spec["ef"] = P(topo.axes)
    batch_spec = P(topo.axes, None)
    return jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec, batch_spec),
        out_specs=(state_spec, P()),
        check_vma=False,
    )


def init_dp_train_state(cfg, opt_cfg: OptimizerConfig, mesh, sync_cfg, key):
    """The :func:`make_dp_train_step` state, created in place on ``mesh``.

    Parameters and Adam moments are made replicated and the error-
    feedback residuals (with ``sync_cfg.error_feedback``) sharded over
    the mesh axes by one jitted init, so no leaf is first built whole on
    one device and copied out from there.
    """
    from ..optim import ef_init
    from .mesh import mesh_topology

    model = build_model(cfg, ShardingPolicy())
    topo = mesh_topology(mesh)
    use_ef = bool(getattr(sync_cfg, "error_feedback", False))

    def init(key):
        params = model.init(key)
        state = {
            "params": params,
            "opt": adamw_init(params, moment_dtype=opt_cfg.moment_dtype),
        }
        if use_ef:
            state["ef"] = ef_init(params, group=topo.group)
        return state

    rep = NamedSharding(mesh, P())
    shardings = {"params": rep, "opt": rep}
    if use_ef:
        shardings["ef"] = NamedSharding(mesh, P(topo.axes))
    return jax.jit(init, out_shardings=shardings)(key)


def make_prefill_step(model, *, tail: int = 128):
    """Forward the prompt; emit logits for the last ``tail`` positions."""

    def prefill_step(params, batch):
        hidden, _ = model.apply(params, batch)
        h_tail = hidden[:, -tail:, :]
        if model.cfg.tie_embeddings:
            head = params["embedding"].T
        else:
            head = params["lm_head"]
        logits = jnp.einsum(
            "bsd,dv->bsv", h_tail, head.astype(h_tail.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits

    return prefill_step


def make_serve_step(model, ctx=None):
    """One-token cached greedy decode — the serving spine's shared step
    (:func:`repro.serve.decode.greedy_step`).  With ``ctx`` the head is
    the tensor-parallel ``CommContext``-routed path; without, the
    model's own head (identical contraction, local)."""
    from ..serve.decode import greedy_step

    return greedy_step(model, ctx)


# ---------------------------------------------------------------------------
# abstract inputs (ShapeDtypeStructs + shardings) for the dry-run
# ---------------------------------------------------------------------------


def _sharded_sds(shape, dtype, mesh, spec):
    if mesh is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    # drop axes that don't divide (mirror ShardingPolicy._fit)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def ok(dim, entry):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        total = int(np.prod([sizes[a] for a in axes]))
        return entry if dim % total == 0 else None

    fitted = P(*(ok(d, e) for d, e in zip(shape, tuple(spec) + (None,) * len(shape))))
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, fitted)
    )


def input_specs(
    arch: str, shape_name: str, mesh: Mesh | None, *, serve2d: bool = False
) -> dict[str, jax.ShapeDtypeStruct]:
    """Abstract batch for one (arch x shape) cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    dp = mesh_dp_axes(mesh) if mesh is not None else None
    if serve2d:
        dp = None  # serving layout: batch replicated, weights 2D-sharded
    tok = functools.partial(
        _sharded_sds, mesh=mesh, spec=P(dp, None), dtype=jnp.int32
    )
    batch: dict[str, Any] = {}
    if shape.kind == "decode":
        batch["tokens"] = tok((B, 1))
        if cfg.frontend == "vision_patches":
            batch["embeds"] = _sharded_sds(
                (B, 1, cfg.d_model), jnp.dtype(cfg.dtype), mesh, P(dp, None, None)
            )
            del batch["tokens"]
        if cfg.encoder_layers:  # enc-dec: encoder context at cache init
            batch["frames"] = _sharded_sds(
                (B, S, cfg.d_model), jnp.dtype(cfg.dtype), mesh,
                P(dp, None, None),
            )
        return batch
    if cfg.frontend == "vision_patches":
        batch["embeds"] = _sharded_sds(
            (B, S, cfg.d_model), jnp.dtype(cfg.dtype), mesh, P(dp, None, None)
        )
        batch["positions"] = _sharded_sds(
            (3, B, S), jnp.int32, mesh, P(None, dp, None)
        )
    else:
        batch["tokens"] = tok((B, S))
    if cfg.encoder_layers:
        batch["frames"] = _sharded_sds(
            (B, S, cfg.d_model), jnp.dtype(cfg.dtype), mesh, P(dp, None, None)
        )
    if shape.kind == "train":
        batch["labels"] = tok((B, S))
        batch["loss_mask"] = _sharded_sds(
            (B, S), jnp.float32, mesh, P(dp, None)
        )
    return batch


def state_specs(
    arch: str,
    shape_name: str,
    mesh: Mesh | None,
    *,
    opt_cfg: OptimizerConfig | None = None,
    seq_parallel: bool = False,
    cfg_overrides: dict | None = None,
    serve2d: bool = False,
):
    """Abstract (state/params/cache) trees with shardings for a cell.

    Returns (model, policy, abstract_tree) where abstract_tree is
    {"params", "opt"} for train, {"params"} for prefill, and
    {"params", "cache"} for decode shapes.
    """
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    policy = make_policy(
        cfg, mesh, seq_parallel=seq_parallel,
        mode="serve2d" if serve2d else "train",
    )
    model = build_model(cfg, policy)
    opt_cfg = opt_cfg or OptimizerConfig(
        moment_dtype="bfloat16" if cfg.param_count() > 1e11 else "float32"
    )

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    out: dict[str, Any] = {"params": params_sds}
    if shape.kind == "train":
        out["opt"] = jax.eval_shape(
            functools.partial(adamw_init, moment_dtype=opt_cfg.moment_dtype),
            params_sds,
        )
    if shape.kind == "decode":
        batch = input_specs(arch, shape_name, mesh, serve2d=serve2d)
        out["cache"] = jax.eval_shape(
            functools.partial(
                model.init_decode,
                batch_size=shape.global_batch,
                max_len=shape.seq_len,
            ),
            params_sds,
            batch=batch if cfg.encoder_layers else None,
        )

    if mesh is not None:
        out["params"] = _attach_param_shardings(out["params"], policy)
        if "opt" in out:
            opt = out["opt"]
            out["opt"] = type(opt)(
                step=jax.ShapeDtypeStruct(
                    (), jnp.int32, sharding=NamedSharding(mesh, P())
                ),
                mu=_attach_param_shardings(opt.mu, policy),
                nu=_attach_param_shardings(opt.nu, policy),
            )
        if "cache" in out:
            out["cache"] = _attach_cache_shardings(out["cache"], policy)
    return model, policy, out, opt_cfg


def _attach_param_shardings(params_sds, policy: ShardingPolicy):
    specs = policy.param_specs(
        jax.tree.map(lambda s: s, params_sds)
    )
    return jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(policy.mesh, spec)
        ),
        params_sds,
        specs,
    )


def _cache_spec(path_leaf_shape, policy: ShardingPolicy, name: str, shape):
    dp, tp = policy.dp, policy.tp_axis
    sizes_ok = lambda dim, axes: dim % _prod_axis(policy, axes) == 0

    if policy.mode == "serve2d":
        joint = ((tp,) if tp else ()) + tuple(policy.fsdp_axes or ())
        if name in ("k", "v"):  # (n_super, B, KV, S, hd): S over the grid
            if sizes_ok(shape[3], joint):
                return P(None, None, None, joint, None)
            return P(None, None, None, tp if sizes_ok(shape[3], (tp,)) else None, None)
        if name == "state":  # mamba (n,B,d_in,N) / rwkv (n,B,H,hd,hd)
            ax = joint if sizes_ok(shape[2], joint) else (
                tp if tp and sizes_ok(shape[2], (tp,)) else None
            )
            return P(None, None, ax)
        if name == "conv":  # (n, B, k, d_in)
            ax = joint if sizes_ok(shape[3], joint) else None
            return P(None, None, None, ax)
        if name == "enc_out":
            return P(None, None, None)
        return P()

    if name in ("k", "v"):  # (n_super, B, KV, size, hd)
        _, B, KV, _, _ = shape
        if tp and KV % policy.tp_size == 0 and sizes_ok(B, dp):
            return P(None, dp, tp, None, None)
        if tp and shape[3] % policy.tp_size == 0:
            return P(None, dp if sizes_ok(B, dp) else None, None, tp, None)
        return P(None, dp if sizes_ok(B, dp) else None, None, None, None)
    if name == "pos":
        return P(None, None)
    if name in ("state",):  # mamba (n,B,d_in,N) / rwkv (n,B,H,hd,hd)
        spec = [None, dp if sizes_ok(shape[1], dp) else None]
        if tp and shape[2] % policy.tp_size == 0:
            spec.append(tp)
        return P(*spec)
    if name in ("conv", "x_prev", "cm_x_prev"):
        return P(None, dp if sizes_ok(shape[1], dp) else None, None)
    if name == "enc_out":
        return P(dp if sizes_ok(shape[0], dp) else None, None, None)
    if name == "index":
        return P()
    return P()


def _prod_axis(policy, axes):
    if not axes:
        return 1
    axes = axes if isinstance(axes, tuple) else (axes,)
    sizes = dict(zip(policy.mesh.axis_names, policy.mesh.devices.shape))
    return int(np.prod([sizes[a] for a in axes]))


def _attach_cache_shardings(cache_sds, policy: ShardingPolicy):
    def walk(node, name):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        spec = _cache_spec(None, policy, name, node.shape)
        return jax.ShapeDtypeStruct(
            node.shape, node.dtype, sharding=NamedSharding(policy.mesh, spec)
        )

    return walk(cache_sds, "")
