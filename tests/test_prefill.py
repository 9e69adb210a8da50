"""``Model.prefill``: one causal forward pass over a padded prompt.

Against the token-by-token ``decode_step`` loop it replaces (reduced
configs, float32 on the CPU): the last real position's logits and the
K/V rows of the real positions agree within 1e-5 relative, ``pos`` and
``index`` are exact and pad rows hold ``pos = -1``.  The bucket a prompt
is padded to does not change what is served after it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduced
from repro.models import build_model

PARALLEL = {"minicpm-2b", "qwen2-72b", "granite-20b", "qwen2-vl-2b"}
MAX_LEN = 24


@pytest.fixture(scope="module", params=["minicpm-2b", "granite-20b"])
def model_params(request):
    model = build_model(reduced(get_config(request.param)))
    return model, jax.jit(model.init)(jax.random.PRNGKey(0))


def _prompt(n_real, bucket, vocab, seed=0):
    toks = np.random.default_rng(seed).integers(1, vocab, size=n_real)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n_real] = toks
    return padded


def _stepwise(model, params, tokens):
    """The token loop: ``decode_step`` over each real token."""
    cache = model.init_decode(params, 1, max_len=MAX_LEN)
    step = jax.jit(model.decode_step)
    for t in range(tokens.shape[1]):
        logits, cache = step(params, cache, jnp.asarray(tokens[:, t : t + 1]))
    return cache, logits[:, -1]


def _rel_gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / np.abs(b).max()


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_parallel_prefill_only_where_the_stack_has_a_parallel_form(arch):
    model = build_model(reduced(get_config(arch)))
    assert (model.prefill is not None) == (arch in PARALLEL)


@pytest.mark.parametrize("bucket", [8, 16])
@pytest.mark.parametrize("where", ["one", "bucket-1", "bucket"])
def test_prefill_matches_the_decode_step_loop(model_params, bucket, where):
    model, params = model_params
    n_real = {"one": 1, "bucket-1": bucket - 1, "bucket": bucket}[where]
    prompt = _prompt(n_real, bucket, model.cfg.vocab_size)
    fn = jax.jit(model.prefill, static_argnums=3)
    cache, logits = fn(params, jnp.asarray(prompt), jnp.int32(n_real), MAX_LEN)
    want_cache, want_logits = _stepwise(model, params, prompt[:, :n_real])

    assert logits.shape == (1, model.cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert _rel_gap(logits, want_logits) < 1e-5
    assert int(cache["index"]) == n_real
    assert jax.tree.structure(cache) == jax.tree.structure(want_cache)
    for got, want in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
        assert got.shape == want.shape and got.dtype == want.dtype
    for sub, layer in cache["stack"].items():
        want = want_cache["stack"][sub]
        pos = np.asarray(layer["pos"])
        np.testing.assert_array_equal(pos, np.asarray(want["pos"]))
        assert (pos[:, :n_real] == np.arange(n_real)).all()
        assert (pos[:, n_real:] == -1).all()
        for kv in ("k", "v"):
            got_rows = np.asarray(layer[kv])[..., :n_real]
            want_rows = np.asarray(want[kv])[..., :n_real]
            assert _rel_gap(got_rows, want_rows) < 1e-5, (sub, kv)


def test_bucket_does_not_change_the_served_tokens(model_params):
    model, params = model_params
    n_real, gen = 6, 5
    fn = jax.jit(model.prefill, static_argnums=3)
    step = jax.jit(model.decode_step)
    served = []
    for bucket in (8, 16):
        prompt = _prompt(n_real, bucket, model.cfg.vocab_size, seed=1)
        cache, logits = fn(params, jnp.asarray(prompt), jnp.int32(n_real), MAX_LEN)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        toks = [int(tok[0, 0])]
        for _ in range(gen):
            logits, cache = step(params, cache, tok)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            toks.append(int(tok[0, 0]))
        served.append(toks)
    assert served[0] == served[1]
