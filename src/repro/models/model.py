"""Public model API: build_model(config) -> Model (pure-function bundle).

Covers all assigned families:
  * decoder-only LMs (dense / MoE / hybrid / SSM),
  * whisper-style encoder-decoder (frames stub -> encoder -> cross-attn),
  * VLM backbone (precomputed patch/frame embeddings + M-RoPE positions).

Training loss is a seq-chunked cross-entropy that never materialises the
full (B, S, V) logits (essential for 256k vocabs at 4k seq).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import transformer as tfm
from .layers import head_dot, mixed_bwd, rms_norm, softcap
from .sharding import ShardingPolicy

__all__ = ["Model", "build_model"]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any
    policy: ShardingPolicy
    init: Callable            # (key) -> params
    apply: Callable           # (params, batch) -> (hidden, aux)
    loss: Callable            # (params, batch) -> (loss, metrics)
    logits: Callable          # (params, batch) -> full logits (small use!)
    init_decode: Callable     # (params, batch, max_len[, batch_data]) -> cache
    decode_step: Callable     # (params, cache, tokens) -> (logits, cache)
    # the head-split decode pair used by the serving spine's tensor-
    # parallel logits path (repro.serve): ``decode_hidden`` is
    # ``decode_step`` up to (and including) the final norm, without the
    # LM head; ``head_weights`` exposes the (D, V) head matrix so a
    # contraction-sharded head can be computed outside the model.
    decode_hidden: Callable = None  # (params, cache, tokens) -> (hidden, cache)
    head_weights: Callable = None   # (params) -> (D, V)
    # the whole padded prompt in one causal forward pass, where the stack
    # has a parallel form (every sublayer global attention with a dense
    # FFN); None where a prompt must be stepped through ``decode_step``.
    prefill: Callable = None  # (params, prompt (1, L), n_real, max_len)
    #                           -> (cache, last logits (1, V) f32)


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _embed_tokens(params, tokens, cfg):
    emb = params["embedding"]
    x = emb[tokens].astype(_dtype(cfg))
    if cfg.scale_embeddings:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


def _head_weights(params, cfg):
    if cfg.tie_embeddings:
        return params["embedding"].T  # (D, V)
    return params["lm_head"]


def _final_hidden(params, batch, cfg, policy, *, causal=True):
    """Embed -> stack -> final norm. Returns (hidden, aux, enc_out)."""
    enc_out = None
    if cfg.encoder_layers:
        frames = batch["frames"].astype(_dtype(cfg))  # stub frontend output
        pos_e = jnp.arange(frames.shape[1])[None]
        enc, _ = tfm.stack_apply(
            params["encoder"], frames, cfg=cfg, policy=policy,
            positions=pos_e, pattern=cfg.encoder_pattern, causal=False,
        )
        enc_out = rms_norm(enc, params["encoder_norm"], cfg.norm_eps)

    if "embeds" in batch:  # VLM stub frontend: precomputed embeddings
        x = batch["embeds"].astype(_dtype(cfg))
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        x = _embed_tokens(params, tokens, cfg)
        B, S = tokens.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = policy.act(x, kind="hidden")
    x, aux = tfm.stack_apply(
        params["stack"], x, cfg=cfg, policy=policy,
        positions=positions, causal=causal, enc_out=enc_out,
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux, enc_out


def _chunked_loss(hidden, head_w, labels, mask, cfg, policy, chunk=512):
    """CE over seq chunks; logits (B, chunk, V) only, never (B, S, V)."""
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S
    n = S // chunk

    def one(i):
        h = lax.dynamic_slice_in_dim(hidden, i * chunk, chunk, axis=1)
        y = lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        m = lax.dynamic_slice_in_dim(mask, i * chunk, chunk, axis=1)
        logits = head_dot(h, head_w.astype(h.dtype))
        logits = softcap(logits, cfg.final_logit_softcap)
        logits = policy.act(logits, kind="logits")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, y[..., None], axis=-1
        )[..., 0]
        nll = (logz - gold) * m
        return nll.sum(), m.sum()

    nll, cnt = 0.0, 0.0
    if n == 1:
        nll, cnt = one(0)
    else:
        (nlls, cnts) = lax.map(one, jnp.arange(n))
        nll, cnt = nlls.sum(), cnts.sum()
    return nll / jnp.maximum(cnt, 1.0)


def build_model(cfg, policy: ShardingPolicy | None = None) -> Model:
    policy = policy or ShardingPolicy()
    dtype = _dtype(cfg)

    # ---- init --------------------------------------------------------------

    def init(key):
        ks = jax.random.split(key, 5)
        params = {
            "embedding": (
                jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model)) * 0.02
            ).astype(dtype),
            "stack": tfm.init_stack(
                ks[1], cfg, dtype, cross=cfg.cross_attention
            ),
            "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = (
                jax.random.normal(ks[2], (cfg.d_model, cfg.vocab_size)) * 0.02
            ).astype(dtype)
        if cfg.encoder_layers:
            params["encoder"] = tfm.init_stack(
                ks[3], cfg, dtype,
                n_layers=cfg.encoder_layers,
                pattern=cfg.encoder_pattern, cross=False,
            )
            params["encoder_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
        return params

    # ---- forward / loss ----------------------------------------------------

    def apply(params, batch):
        with mixed_bwd(getattr(cfg, "bf16_bwd", False)):
            return _final_hidden(params, batch, cfg, policy)[:2]

    def loss(params, batch):
        with mixed_bwd(getattr(cfg, "bf16_bwd", False)):
            return _loss_inner(params, batch)

    def _loss_inner(params, batch):
        hidden, aux, _ = _final_hidden(params, batch, cfg, policy)
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)))
        mask = batch.get(
            "loss_mask", jnp.ones(labels.shape, jnp.float32)
        )
        ce = _chunked_loss(
            hidden, _head_weights(params, cfg), labels, mask, cfg, policy
        )
        total = ce + aux
        return total, {"loss": total, "ce": ce, "aux": aux}

    def logits_fn(params, batch):
        hidden, _, _ = _final_hidden(params, batch, cfg, policy)
        logits = jnp.einsum(
            "bsd,dv->bsv", hidden, _head_weights(params, cfg).astype(hidden.dtype),
            preferred_element_type=jnp.float32,
        )
        return softcap(logits, cfg.final_logit_softcap)

    # ---- decode ------------------------------------------------------------

    def init_decode(params, batch_size, max_len, batch=None):
        cache = {
            "index": jnp.zeros((), jnp.int32),
            "stack": tfm.init_stack_cache(cfg, batch_size, max_len, dtype),
        }
        if cfg.encoder_layers:
            assert batch is not None and "frames" in batch, (
                "enc-dec decode needs encoder frames at cache init"
            )
            frames = batch["frames"].astype(dtype)
            pos_e = jnp.arange(frames.shape[1])[None]
            enc, _ = tfm.stack_apply(
                params["encoder"], frames, cfg=cfg, policy=policy,
                positions=pos_e, pattern=cfg.encoder_pattern, causal=False,
            )
            cache["enc_out"] = rms_norm(
                enc, params["encoder_norm"], cfg.norm_eps
            )
        return cache

    def decode_hidden(params, cache, tokens):
        """tokens: (B, 1) int32 (or (B, 1, D) embeds for VLM stubs).

        One cached decode step up to (and including) the final norm —
        everything but the LM head.  ``decode_step`` is exactly this
        plus the head einsum, so a caller that computes the head itself
        (the serving spine's contraction-sharded tensor-parallel logits
        path) advances the cache identically to the plain step.
        """
        index = cache["index"]
        if tokens.ndim == 3:
            x = tokens.astype(dtype)
        else:
            x = _embed_tokens(params, tokens, cfg)
        x = policy.act(x, kind="hidden")
        x, new_stack = tfm.stack_decode(
            params["stack"], x, cache["stack"], index,
            cfg=cfg, policy=policy, enc_out=cache.get("enc_out"),
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        new_cache = dict(cache, index=index + 1, stack=new_stack)
        return x, new_cache

    def head(params, x):
        logits = jnp.einsum(
            "bsd,dv->bsv", x, _head_weights(params, cfg).astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        logits = softcap(logits, cfg.final_logit_softcap)
        return policy.act(logits, kind="logits")

    def decode_step(params, cache, tokens):
        """tokens: (B, 1) int32 (or (B, 1, D) embeds for VLM stubs)."""
        x, new_cache = decode_hidden(params, cache, tokens)
        return head(params, x), new_cache

    def prefill(params, prompt, n_real, max_len):
        """prompt: (1, L) int32, its first ``n_real`` tokens real.

        The cache ``decode_step`` leaves after the ``n_real`` real
        tokens (index ``n_real``; pad rows hold ``pos = -1``) and the
        logits (1, V) at the last of them, from one causal forward pass
        over all L positions.  The head runs on that one row only.
        """
        L = prompt.shape[1]
        if L > max_len:
            raise ValueError(f"prompt of {L} positions exceeds max_len {max_len}")
        x = policy.act(_embed_tokens(params, prompt, cfg), kind="hidden")
        x, stack = tfm.stack_prefill(
            params["stack"], x, n_real, cfg=cfg, policy=policy, size=max_len
        )
        x = lax.dynamic_slice_in_dim(x, n_real - 1, 1, axis=1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        cache = {"index": jnp.asarray(n_real, jnp.int32), "stack": stack}
        return cache, head(params, x)[:, 0]

    parallel = (
        cfg.is_decoder_only
        and not cfg.cross_attention
        and not cfg.sandwich_norm
        and all(s.mixer == "attn" and s.ffn == "dense" for s in cfg.pattern)
    )

    return Model(
        cfg=cfg,
        policy=policy,
        init=init,
        apply=apply,
        loss=loss,
        logits=logits_fn,
        init_decode=init_decode,
        decode_step=decode_step,
        decode_hidden=decode_hidden,
        head_weights=lambda params: _head_weights(params, cfg),
        prefill=prefill if parallel else None,
    )
