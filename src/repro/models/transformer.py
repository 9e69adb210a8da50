"""Decoder stack: super-layer pattern, scan-over-layers, remat, caches.

A model is ``num_super_layers`` repetitions of the config's sublayer
*pattern* (DESIGN.md §3).  Per-sublayer parameters are stacked along a
leading ``n_super`` dim and the super-layer body is ``lax.scan``-ned
(keeps the HLO one-body-deep — essential for 512-device compiles of
80-layer models) with a configurable remat policy.

Mixer kinds: "attn" (global), "attn_local" (sliding window), "mamba",
"rwkv6".  FFN kinds: "dense" GLU, "moe" (EP), and the implicit RWKV
channel-mix when the mixer is rwkv6.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import attention as attn_mod
from . import mamba as mamba_mod
from . import moe as moe_mod
from . import rwkv as rwkv_mod
from .layers import glu_mlp, init_glu_mlp, rms_norm

__all__ = [
    "init_stack",
    "stack_apply",
    "stack_decode",
    "stack_prefill",
    "init_stack_cache",
]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_sublayer(key, sub, cfg, dtype, *, cross: bool):
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {"norm1": jnp.zeros((cfg.d_model,), jnp.float32)}
    if sub.mixer in ("attn", "attn_local"):
        p["mixer"] = attn_mod.init_attention(ks[0], cfg, dtype)
    elif sub.mixer == "mamba":
        p["mixer"] = mamba_mod.init_mamba(ks[0], cfg, dtype)
    elif sub.mixer == "rwkv6":
        p["mixer"] = rwkv_mod.init_rwkv(ks[0], cfg, dtype)
    if cross:
        p["norm_cross"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["cross"] = attn_mod.init_attention(ks[1], cfg, dtype, cross=True)
    if sub.mixer == "rwkv6":
        p["ffn"] = rwkv_mod.init_rwkv_cm(ks[2], cfg, dtype)
    elif sub.ffn == "dense":
        p["ffn"] = init_glu_mlp(ks[2], cfg.d_model, cfg.d_ff, dtype)
    elif sub.ffn == "moe":
        p["ffn"] = moe_mod.init_moe(ks[2], cfg, dtype)
    if sub.ffn != "none" or sub.mixer == "rwkv6":
        p["norm2"] = jnp.zeros((cfg.d_model,), jnp.float32)
    if cfg.sandwich_norm:
        p["norm1_post"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["norm2_post"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


def init_stack(
    key, cfg, dtype, *, n_layers: int | None = None, pattern=None,
    cross: bool = False,
):
    """Stacked params: {"sub<i>": pytree with leading n_super dim}."""
    pattern = pattern if pattern is not None else cfg.pattern
    n_super = (n_layers or cfg.num_layers) // len(pattern)
    keys = jax.random.split(key, n_super)

    def init_one(k):
        sks = jax.random.split(k, len(pattern))
        return {
            f"sub{i}": _init_sublayer(sks[i], sub, cfg, dtype, cross=cross)
            for i, sub in enumerate(pattern)
        }

    return jax.vmap(init_one)(keys)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _sublayer_full(p, x, sub, *, cfg, policy, positions, causal, enc_out):
    def maybe_post(h, name):
        if cfg.sandwich_norm:
            return rms_norm(h, p[name], cfg.norm_eps)
        return h

    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if sub.mixer in ("attn", "attn_local"):
        window = cfg.sliding_window if sub.mixer == "attn_local" else None
        h = attn_mod.attention_full(
            p["mixer"], h, cfg=cfg, policy=policy, positions=positions,
            causal=causal, window=window,
        )
    elif sub.mixer == "mamba":
        h = mamba_mod.mamba_full(p["mixer"], h, cfg=cfg, policy=policy)
    elif sub.mixer == "rwkv6":
        h = rwkv_mod.rwkv_full(p["mixer"], h, cfg=cfg, policy=policy)
    else:
        h = jnp.zeros_like(h)
    x = x + maybe_post(h, "norm1_post")

    if "cross" in p:
        h = rms_norm(x, p["norm_cross"], cfg.norm_eps)
        h = attn_mod.attention_full(
            p["cross"], h, cfg=cfg, policy=policy, positions=positions,
            causal=False, kv_src=enc_out,
        )
        x = x + h

    aux = jnp.zeros((), jnp.float32)
    if "ffn" in p:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if sub.mixer == "rwkv6":
            h = rwkv_mod.rwkv_cm_full(p["ffn"], h, cfg=cfg)
        elif sub.ffn == "moe":
            h, aux = moe_mod.moe_apply(p["ffn"], h, cfg=cfg, policy=policy)
        else:
            h = glu_mlp(p["ffn"], h, cfg.act)
        x = x + maybe_post(h, "norm2_post")
    return x, aux


_REMAT_POLICIES = {
    "full": None,
    "dots": "dots_saveable",
    "none": "none",
}


def _remat_wrap(body, remat: str):
    if remat == "none":
        return body
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_saveable
        )
    return jax.checkpoint(body)


def stack_apply(
    stack_params,
    x: jax.Array,
    *,
    cfg,
    policy,
    positions,
    pattern=None,
    causal: bool = True,
    enc_out: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Run the scanned stack. Returns (hidden, summed aux loss)."""
    pattern = pattern if pattern is not None else cfg.pattern

    def body(carry, layer_params):
        h, aux = carry
        h = policy.act(h, kind="hidden")
        for i, sub in enumerate(pattern):
            h, a = _sublayer_full(
                layer_params[f"sub{i}"], h, sub,
                cfg=cfg, policy=policy, positions=positions,
                causal=causal, enc_out=enc_out,
            )
            aux = aux + a
        return (h, aux), None

    body = _remat_wrap(body, cfg.remat)
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), stack_params)
    return x, aux


def stack_prefill(
    stack_params, x: jax.Array, n_real: jax.Array, *, cfg, policy, size: int
):
    """Causal forward of a padded prompt through the scanned stack that
    also fills its decode cache: returns ``(x, cache)``, the cache laid
    out as ``init_stack_cache(cfg, B, size, ...)`` after ``n_real`` real
    positions (see ``attention_prefill``).  Only for stacks whose every
    sublayer is global ``"attn"`` with a dense FFN.
    """

    def body(h, layer_params):
        h = policy.act(h, kind="hidden")
        cache = {}
        for i in range(len(cfg.pattern)):
            p = layer_params[f"sub{i}"]
            a, cache[f"sub{i}"] = attn_mod.attention_prefill(
                p["mixer"], rms_norm(h, p["norm1"], cfg.norm_eps), n_real,
                cfg=cfg, policy=policy, size=size,
            )
            h = h + a
            h = h + glu_mlp(
                p["ffn"], rms_norm(h, p["norm2"], cfg.norm_eps), cfg.act
            )
        return h, cache

    return lax.scan(body, x, stack_params)


# ---------------------------------------------------------------------------
# decode (single token, cached)
# ---------------------------------------------------------------------------


def init_stack_cache(
    cfg, batch: int, max_len: int, dtype, *, pattern=None,
    n_layers: int | None = None,
):
    """Cache pytree mirroring the stack: leaves (n_super, ...)."""
    pattern = pattern if pattern is not None else cfg.pattern
    n_super = (n_layers or cfg.num_layers) // len(pattern)

    def one(sub):
        if sub.mixer in ("attn", "attn_local"):
            window = cfg.sliding_window if sub.mixer == "attn_local" else None
            return attn_mod.init_cache(
                cfg, batch, max_len, window=window, dtype=dtype
            )
        if sub.mixer == "mamba":
            return mamba_mod.init_mamba_cache(cfg, batch, dtype)
        if sub.mixer == "rwkv6":
            c = rwkv_mod.init_rwkv_cache(cfg, batch, dtype)
            c["cm_x_prev"] = jnp.zeros((batch, cfg.d_model), dtype)
            return c
        return {}

    return {
        f"sub{i}": jax.tree.map(
            lambda l: jnp.broadcast_to(l, (n_super,) + l.shape).copy(),
            one(sub),
        )
        for i, sub in enumerate(pattern)
    }


def _sublayer_decode(p, x, cache, sub, *, cfg, policy, index, enc_out):
    def maybe_post(h, name):
        if cfg.sandwich_norm:
            return rms_norm(h, p[name], cfg.norm_eps)
        return h

    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if sub.mixer in ("attn", "attn_local"):
        window = cfg.sliding_window if sub.mixer == "attn_local" else None
        h, cache = attn_mod.attention_decode(
            p["mixer"], h, cache, index, cfg=cfg, policy=policy, window=window
        )
    elif sub.mixer == "mamba":
        h, cache = mamba_mod.mamba_decode(
            p["mixer"], h, cache, cfg=cfg, policy=policy
        )
    elif sub.mixer == "rwkv6":
        cache = dict(cache)
        cm_prev = cache.pop("cm_x_prev")
        h, cache = rwkv_mod.rwkv_decode(
            p["mixer"], h, cache, cfg=cfg, policy=policy
        )
        cache["cm_x_prev"] = cm_prev
    x = x + maybe_post(h, "norm1_post")

    if "cross" in p:
        h = rms_norm(x, p["norm_cross"], cfg.norm_eps)
        h, _ = attn_mod.attention_decode(
            p["cross"], h, {}, index, cfg=cfg, policy=policy, kv_src=enc_out
        )
        x = x + h

    if "ffn" in p:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if sub.mixer == "rwkv6":
            h, new_prev = rwkv_mod.rwkv_cm_decode(
                p["ffn"], h, cache["cm_x_prev"], cfg=cfg
            )
            cache = dict(cache, cm_x_prev=new_prev)
        elif sub.ffn == "moe":
            h, _ = moe_mod.moe_apply(p["ffn"], h, cfg=cfg, policy=policy)
        else:
            h = glu_mlp(p["ffn"], h, cfg.act)
        x = x + maybe_post(h, "norm2_post")
    return x, cache


def stack_decode(
    stack_params,
    x: jax.Array,
    cache,
    index,
    *,
    cfg,
    policy,
    pattern=None,
    enc_out: jax.Array | None = None,
):
    """One-token decode through the scanned stack: returns (x, new cache).

    The cache rides the scan carry and each layer's entry is written
    back in place.  Scanning it as ``xs``/``ys`` instead would build the
    new cache in a second buffer the size of the whole cache, and a
    batching transform (the serving engine's slot ``vmap``) would also
    transpose it into layer-major order and back on every step.
    """
    pattern = pattern if pattern is not None else cfg.pattern

    def body(carry, layer_params):
        h, cache, layer = carry
        h = policy.act(h, kind="hidden")
        layer_cache = jax.tree.map(
            lambda c: lax.dynamic_index_in_dim(c, layer, keepdims=False),
            cache,
        )
        new_cache = {}
        for i, sub in enumerate(pattern):
            h, new_cache[f"sub{i}"] = _sublayer_decode(
                layer_params[f"sub{i}"], h, layer_cache[f"sub{i}"], sub,
                cfg=cfg, policy=policy, index=index, enc_out=enc_out,
            )
        cache = jax.tree.map(
            lambda c, n: lax.dynamic_update_index_in_dim(c, n, layer, 0),
            cache, new_cache,
        )
        return (h, cache, layer + 1), None

    (x, new_cache, _), _ = lax.scan(
        body, (x, cache, jnp.zeros((), jnp.int32)), stack_params
    )
    return x, new_cache
