"""Plain float32 reference of a llama-style dense decoder (minicpm-2b as
this repo runs it), and the weights every run of the benchmark uses.

It imports nothing of the program.  The layer equations, in the order a
token meets them::

    x   = E[token]                                   (tied embedding E)
    per layer:
      h = rms(x) * (1 + g1)                          rms(x) = x / sqrt(mean(x^2) + eps)
      q, k, v = h Wq, h Wk, h Wv; rope(q), rope(k)   rotate halves, theta^(-i/(hd/2))
      x = x + softmax(q k^T / sqrt(hd), causal) v Wo
      h = rms(x) * (1 + g2)
      x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x) * (1 + gf)) E^T

Weights are drawn on the device from the seed, in the type they are
served in (bfloat16 matrices, float32 norm gains).  The reference upcasts
them and computes everything in float32 at ``highest`` matmul precision.
With ``precision="fp8"`` every matmul operand is first rounded to
float8 under a per-tensor scale (e4m3 forward, e5m2 for the gradients
flowing back): the control, which a run must be able to tell apart from
the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


# --- weights ------------------------------------------------------------------


def layer_weights(key, layer, cfg):
    """One layer's weights: matrices N(0, 1/fan_in) in bfloat16, norm
    gains N(0, 0.05^2) in float32 (applied as 1 + g)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    ks = jax.random.split(jax.random.fold_in(key, layer), 9)

    def mat(k, n_in, n_out):
        w = jax.random.normal(k, (n_in, n_out), jnp.float32) / math.sqrt(n_in)
        return w.astype(jnp.bfloat16)

    def gain(k):
        return 0.05 * jax.random.normal(k, (d,), jnp.float32)

    return {
        "attn_norm": gain(ks[0]),
        "wq": mat(ks[1], d, q),
        "wk": mat(ks[2], d, kv),
        "wv": mat(ks[3], d, kv),
        "wo": mat(ks[4], q, d),
        "mlp_norm": gain(ks[5]),
        "w_gate": mat(ks[6], d, f),
        "w_up": mat(ks[7], d, f),
        "w_down": mat(ks[8], f, d),
    }


def make_weights(seed32: int, cfg, layers: int):
    """All weights in one jitted call: ``{"embed", "final_norm",
    "layers"}`` with every layer leaf stacked on a leading axis."""
    v, d = cfg["vocab_size"], cfg["hidden_size"]

    def build(key):
        k_emb, k_norm, k_layers = jax.random.split(key, 3)
        return {
            "embed": (0.02 * jax.random.normal(k_emb, (v, d), jnp.float32)).astype(jnp.bfloat16),
            "final_norm": 0.05 * jax.random.normal(k_norm, (d,), jnp.float32),
            "layers": jax.lax.map(
                lambda i: layer_weights(k_layers, i, cfg), jnp.arange(layers)
            ),
        }

    return build, jax.random.PRNGKey(seed32)


# --- arithmetic ---------------------------------------------------------------


def _round8(x, dtype, top):
    """Round to an 8-bit float type under a per-tensor scale (max |x|
    -> the type's largest finite value)."""
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    """float8 operand as fp8 training rounds it: e4m3 forward, and the
    cotangent that flows back through it in e5m2, each under its own
    per-tensor scale."""
    return _round8(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_round8(g, jnp.float8_e5m2, 57344.0),))


def _mm(spec, a, b, precision):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(x, w, cfg, precision):
    """One layer over one sequence ``x (S, D)``, causal."""
    S = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(S)
    h = _rms(x, w["attn_norm"], eps)
    q = _rope(_mm("sd,dk->sk", h, w["wq"], precision).reshape(S, H, hd), pos, theta)
    k = _rope(_mm("sd,dk->sk", h, w["wk"], precision).reshape(S, KV, hd), pos, theta)
    v = _mm("sd,dk->sk", h, w["wv"], precision).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = _mm("qhd,khd->hqk", q, k, precision) / math.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("hqk,khd->qhd", p, v, precision).reshape(S, H * hd)
    x = x + _mm("sk,kd->sd", o, w["wo"], precision)
    h = _rms(x, w["mlp_norm"], eps)
    a = jax.nn.silu(_mm("sd,df->sf", h, w["w_gate"], precision))
    u = _mm("sd,df->sf", h, w["w_up"], precision)
    return x + _mm("sf,fd->sd", a * u, w["w_down"], precision)


def hidden(weights, tokens, cfg, precision="f32"):
    """Final normed hidden states of one sequence ``tokens (S,)``."""
    x = weights["embed"][tokens].astype(jnp.float32)

    def body(x, w):
        return block(x, w, cfg, precision), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    return _rms(x, weights["final_norm"], cfg["rms_norm_eps"])


def logits_at(weights, tokens, rows, cfg, precision="f32"):
    """Logits ``(len(rows), V)`` at positions ``rows`` of ``tokens``."""
    h = hidden(weights, tokens, cfg, precision)[rows]
    return _mm("sd,vd->sv", h, weights["embed"], precision)


def sequence_nll(weights, tokens, labels, mask, cfg, precision="f32"):
    """Summed masked cross-entropy of one sequence."""
    h = hidden(weights, tokens, cfg, precision)
    lg = _mm("sd,vd->sv", h, weights["embed"], precision)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((logz - gold) * mask)


# --- training -----------------------------------------------------------------


def step_loss(weights, batch, cfg, precision="f32"):
    """Loss of a data-parallel step: the mean over the batch's blocks
    (one block of rows per chip) of each block's masked mean
    cross-entropy.  ``batch`` arrays are ``(blocks, rows, S)``.  Rows are
    recomputed in the backward pass, one at a time, so the reference
    fits beside its optimizer state."""
    nb = batch["tokens"].shape[0]
    count = jnp.maximum(jnp.sum(batch["loss_mask"], axis=(1, 2)), 1.0)  # (blocks,)
    flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in batch.items()}
    row_w = jnp.repeat(1.0 / (count * nb), batch["tokens"].shape[1])

    @jax.checkpoint
    def nll(w, r):
        return sequence_nll(w, r["tokens"], r["labels"], r["loss_mask"], cfg, precision)

    def body(acc, xs):
        r, rw = xs
        return acc + rw * nll(weights, r), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (flat, row_w))
    return total


def train_readings(make_w0, batches, cfg, opt, precision="f32"):
    """Follow the first ``len(batches)`` AdamW steps from the weights
    ``make_w0()`` makes (called twice: the start is made again at the
    end rather than held through the steps).

    Returns each step's loss, each leaf's norm of the first gradient as
    the optimizer takes it (after global-norm clipping), each leaf's
    norm of the weights' change over all the steps, and each leaf's
    unclipped first-gradient norm.  Weights keep their type (bfloat16
    matrices are rounded after every update); the moments are float32
    and wait on the host between steps, so the chip holds only the
    weights, one gradient and one leaf's moments at a time.
    """
    b1, b2 = opt["betas"]
    up = lambda w: jax.tree.map(lambda a: a.astype(jnp.float32), w)  # noqa: E731
    vg = jax.jit(lambda w, b: jax.value_and_grad(
        lambda w32: step_loss(w32, b, cfg, precision))(up(w)))

    @jax.jit
    def upd(p, g, m, v, scale, lr, c1, c2, decay):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        p32 = p.astype(jnp.float32)
        return (p32 - lr * (u + decay * opt["weight_decay"] * p32)).astype(p.dtype), m, v

    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in t])
    w, tdef = jax.tree.flatten(make_w0())
    moments = [None] * len(w)
    losses = []
    g0 = g0_raw = None
    for t, batch in enumerate(batches):
        loss, g = vg(tdef.unflatten(w), batch)
        g = tdef.flatten_up_to(g)
        raw = [float(x) for x in norms(g)]
        gnorm = math.sqrt(sum(x * x for x in raw))
        scale = min(1.0, opt["grad_clip"] / max(gnorm, 1e-12))
        if t == 0:
            g0_raw, g0 = raw, [x * scale for x in raw]
        lr = opt["lr"] * min(t / max(opt["warmup_steps"], 1), 1.0)
        c1, c2 = 1.0 - b1 ** (t + 1), 1.0 - b2 ** (t + 1)
        new = []
        for i, (p, gi) in enumerate(zip(w, g)):
            if moments[i] is None:
                m = v = jnp.zeros(gi.shape, jnp.float32)
            else:
                m, v = (jnp.asarray(a) for a in moments[i])
            p, m, v = upd(p, gi, m, v, scale, lr, c1, c2, float(p.ndim >= 2))
            moments[i] = (np.asarray(m), np.asarray(v))
            new.append(p)
        del g, m, v
        w = new
        losses.append(float(loss))
    dnorm = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
                                  for x, y in zip(a, b)])
    dp = [float(x) for x in dnorm(w, jax.tree.leaves(make_w0()))]
    return {"losses": losses, "grad": g0, "change": dp, "grad_raw": g0_raw, "leaves": tdef}
