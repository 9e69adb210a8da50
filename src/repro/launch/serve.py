"""Batched serving driver: prefill (chunked) + cached greedy decode.

A minimal production shape: requests are batched, the prompt is prefilled
token-group-wise through ``decode_step`` (filling the KV/state caches),
then decoded greedily inside one jitted ``lax.while_loop``
(:func:`repro.serve.decode.make_decode_loop`).  Works for every decoder
arch including the hybrid/SSM families (their caches are states, not KV).

This module is now a **thin wrapper over the serving spine**
(:mod:`repro.serve`): the decode loop, the lint-clean EOS early exit and
the tensor-parallel head all live there, shared with the
continuous-batching :class:`repro.serve.ServeEngine`.  What remains
here is the fixed-batch driver shape — every request enters and leaves
together — kept because it is the right tool for offline eval sweeps
and as the serial reference the engine's continuous batching is tested
bitwise against.  For request-level serving (admission, in-flight
insertion, replica routing) use :mod:`repro.serve`.

With a ``CommContext`` bound, the early-exit predicate ("every sequence
hit EOS") is agreed across the serving group with a tiny
``ctx.allreduce(..., op="min")`` each step.  The seed-era shape — each
rank testing only its *local* done flags — is exactly what the spmd
lint's collective-uniformity rule rejects: ranks would disagree on
whether the next iteration (and any collective inside it) is reached,
the static signature of a decode-time hang.  With ``mesh`` given,
:func:`serve_batch` shard_maps prefill + decode over the batch and
routes the stop flag through the comm layer.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \\
      --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..configs import get_config, reduced
from ..core import comm
from ..models import build_model
from ..runtime import enable_compile_cache
from ..serve.decode import make_decode_loop  # noqa: F401  (re-export)
from .steps import make_policy, make_serve_step  # noqa: F401  (re-export)


def make_serve_shard(model, ctx: comm.CommContext | None, *, gen_len: int,
                     max_len: int, eos_id: int | None = None):
    """The per-shard serve program: prefill (``fori_loop``) + decode
    loop, everything traced — this is the function the ``--spmd`` sweep
    lints as "the serve decode step"."""
    decode = make_decode_loop(model, ctx, gen_len=gen_len, eos_id=eos_id)

    def shard_fn(params, prompts):
        _b, p = prompts.shape
        cache = model.init_decode(
            params, prompts.shape[0], max_len=max_len, batch=None
        )
        logits, cache = model.decode_step(params, cache, prompts[:, :1])

        def pre_body(t, carry):
            _logits, cache = carry
            step_tok = lax.dynamic_slice_in_dim(prompts, t, 1, axis=1)
            return model.decode_step(params, cache, step_tok)

        logits, cache = lax.fori_loop(1, p, pre_body, (logits, cache))
        tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        return decode(params, cache, tok)

    return shard_fn


def serve_batch(
    model,
    params,
    prompts: jnp.ndarray,
    *,
    gen_len: int,
    max_len: int | None = None,
    batch_extras: dict | None = None,
    mesh=None,
    ctx: comm.CommContext | None = None,
    eos_id: int | None = None,
):
    """prompts: (B, P) int32. Returns (B, gen_len) generated tokens.

    Single-host default: prefill with a jitted per-token step, then run
    :func:`make_decode_loop`.  With ``mesh`` the batch is sharded over
    the mesh's joint axes and the whole prefill + decode runs inside
    one ``shard_map``, with the decode early-exit routed through
    ``ctx`` (built from the mesh if not given) — the comm-layer path
    the ``--spmd`` sweep lints.
    """
    B, P_len = prompts.shape
    max_len = max_len or (P_len + gen_len)

    if mesh is None:
        cache = model.init_decode(
            params, B, max_len=max_len, batch=batch_extras
        )
        step = jax.jit(model.decode_step)
        logits = None
        for t in range(P_len):  # prefill via teacher forcing (cache fill)
            logits, cache = step(params, cache, prompts[:, t : t + 1])
        tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        decode = jax.jit(
            make_decode_loop(model, ctx, gen_len=gen_len, eos_id=eos_id)
        )
        return decode(params, cache, tok)

    if batch_extras is not None:
        raise NotImplementedError(
            "batch_extras (encoder frames) are not supported on the "
            "meshed serve path yet"
        )
    if ctx is None:
        ctx = comm.CommContext(comm.Topology.from_mesh(mesh))
    joint = ctx.topology.axes
    shards = int(np.prod([mesh.shape[a] for a in joint]))
    if B % shards:
        raise ValueError(
            f"batch {B} does not shard over {shards} chips ({joint})"
        )
    shard_fn = make_serve_shard(
        model, ctx, gen_len=gen_len, max_len=max_len, eos_id=eos_id
    )
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(joint)),
        out_specs=P(joint),
        check_vma=False,
    )
    return jax.jit(fn)(params, prompts)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--eos-id", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg, make_policy(cfg, None))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))

    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size
    ).astype(jnp.int32)
    extras = None
    if cfg.encoder_layers:
        extras = {
            "frames": jax.random.normal(
                jax.random.PRNGKey(2), (args.batch, 16, cfg.d_model)
            ).astype(jnp.dtype(cfg.dtype))
        }
    t0 = time.time()
    gen = serve_batch(
        model, params, prompts, gen_len=args.gen, batch_extras=extras,
        eos_id=args.eos_id,
    )
    dt = time.time() - t0
    toks = args.batch * (args.prompt_len + args.gen)
    print(
        f"generated {gen.shape} tokens; {toks/dt:.1f} tok/s total "
        f"({dt:.2f}s wall)"
    )
    print(np.asarray(gen[:2]))


if __name__ == "__main__":
    main()
