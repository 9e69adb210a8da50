"""The program's spans and scopes (``repro.runtime.tracing``).

* The serving engine's host spans, read back from a profiler trace of a
  few reduced ``ServeEngine`` steps: ``serve.admit`` holds the
  ``serve.prefill`` of each admission, then ``serve.slice``,
  ``serve.wait`` and ``serve.record`` follow, with their counts.
* ``ServeEngine.traces``: warmed-up steps trace nothing.
* ``ServeEngine.prefills`` and the ``mode`` of ``serve.prefill``: a
  model with ``Model.prefill`` (minicpm-2b) prefills in parallel.
* The persistent compile cache (``enable_compile_cache``) hands back
  executables that carry their own program's scopes.
* Device scopes, read from the ``op_name`` of compiled programs: NAP's
  and MLA's phases (``repro.comm.<engine>/intra|inter``) and the train
  steps' parts.  The multi-device programs compile on four virtual
  devices in a subprocess (``_scope_checks.py``), which must set
  XLA_FLAGS before jax starts.
"""

import dataclasses
import glob
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from repro.runtime import scope, span

_SCRIPT = Path(__file__).parent / "_scope_checks.py"
_SRC = str(Path(__file__).parent.parent / "src")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def _repro_scopes(op_name: str) -> list[str]:
    return [p for p in op_name.split("/") if p.startswith("repro.")]


# ---------------------------------------------------------------------------
# the tracing module itself


def test_scope_roots_names_and_phases_stay_bare():
    def f(x):
        with scope("comm.nap"):
            with scope("inter", phase=True):
                return jax.numpy.sin(x)

    text = jax.jit(f).lower(1.0).as_text(debug_info=True)
    assert "jit(f)/repro.comm.nap/inter/sin" in text


def test_span_counts_become_trace_stats(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with span("serve.prefill", bucket=256, prompt=100) as s:
            s.set_metadata(extra=3)
    events = _repro_events(tmp_path)
    assert [(e[0], e[3]) for e in events] == [
        ("repro.serve.prefill", {"bucket": 256, "prompt": 100, "extra": 3})
    ]


_CACHED_SCOPES = """
import jax, jax.numpy as jnp
from repro.runtime import enable_compile_cache, scope
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
for name in ("train.a", "train.b"):
    def f(x, name=name):
        with scope(name):
            return jnp.sin(x) * 2
    text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
    print(name, "repro." + name in text)
"""


def test_cached_executables_keep_their_own_scopes(tmp_path):
    # two programs that differ only in a scope's name: the second must
    # not be served the first's executable, whose op names are stale
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _CACHED_SCOPES], capture_output=True,
        text=True, env=env, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.split() == ["train.a", "True", "train.b", "True"]
    assert any(tmp_path.iterdir())  # the cache was written


# ---------------------------------------------------------------------------
# the serving engine's spans and trace counter


def _engine():
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serve import PromptBuckets, ServeEngine

    model = build_model(reduced(get_config("minicpm-2b")))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return ServeEngine(
        model, params, num_slots=2, max_len=32,
        buckets=PromptBuckets([4, 8]),
    )


def _repro_events(trace_dir):
    """``(name, start, end, stats)`` of every ``repro.*`` host span."""
    files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(sorted(files)[-1])
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [
                    (e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events
                    if e.name.startswith("repro.")
                ]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_engine_step_spans_nest_and_carry_counts(tmp_path):
    engine = _engine()
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5, 3], [2, 7, 1, 8, 2, 8]]
    for p in prompts:
        engine.submit(p, 2)
    engine.step()  # compiles the slice and both buckets' prefills
    generated = lambda: sum(  # noqa: E731
        len(r.generated) for r in engine.scheduler.requests.values()
    )
    before = generated()
    with jax.profiler.trace(str(tmp_path)):
        steps = 0
        while not engine.idle:
            engine.step()
            steps += 1
    events = _repro_events(tmp_path)
    names = [e[0] for e in events]
    assert set(names) == {
        "repro.serve.admit", "repro.serve.prefill", "repro.serve.slice",
        "repro.serve.wait", "repro.serve.record",
    }
    admits = [e for e in events if e[0] == "repro.serve.admit"]
    assert len(admits) == steps
    # the two requests admitted after the first step prefill inside
    # the admit span of their step
    prefills = [e for e in events if e[0] == "repro.serve.prefill"]
    assert len(prefills) == 2
    for _, start, end, stats in prefills:
        assert any(a[1] <= start and end <= a[2] for a in admits)
        assert stats["bucket"] in (4, 8) and 1 <= stats["prompt"] <= stats["bucket"]
    assert sorted(s["prompt"] for *_, s in prefills) == [2, 6]
    # each step's spans follow one another: admit, slice, wait, record
    top = [e for e in events if e[0] != "repro.serve.prefill"]
    order = ["repro.serve.admit", "repro.serve.slice", "repro.serve.wait",
             "repro.serve.record"]
    assert [e[0] for e in top] == order * steps
    assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))
    records = [e[3] for e in top if e[0] == "repro.serve.record"]
    assert all(r["steps"] == 1 and 1 <= r["tokens"] <= 2 for r in records)
    assert sum(r["tokens"] for r in records) == generated() - before


def test_engine_traces_unchanged_by_steady_state_steps():
    engine = _engine()
    for p in ([3, 1, 4], [1, 5, 9, 2, 6]):
        engine.submit(p, 3)
    engine.run()  # warm-up: both buckets, the slice, the scatters
    warm = dict(engine.traces)
    assert set(warm) == {"slice", "prefill/4", "prefill/8", "scatter", "set_tok"}
    for p in ([2, 7], [1, 8, 2, 8, 1], [4, 5, 9], [6, 6, 6, 6, 6, 6, 6]):
        engine.submit(p, 4)
    engine.run()
    assert dict(engine.traces) == warm


def test_minicpm_engine_prefills_in_one_parallel_pass(tmp_path):
    engine = _engine()
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5, 3], [2, 7, 1, 8, 2, 8]]
    for p in prompts[:2]:
        engine.submit(p, 2)
    engine.run()  # warm-up: both buckets' prefills
    warm = dict(engine.traces)
    with jax.profiler.trace(str(tmp_path)):
        for p in prompts[2:]:
            engine.submit(p, 2)
        engine.run()
    assert engine.prefills == {"parallel": len(prompts)}
    prefills = [e for e in _repro_events(tmp_path)
                if e[0] == "repro.serve.prefill"]
    assert len(prefills) == 2
    assert all(stats["mode"] == "parallel" for *_, stats in prefills)
    assert engine.traces["prefill/4"] == engine.traces["prefill/8"] == 1
    assert dict(engine.traces) == warm


def test_make_train_step_scopes_its_parts():
    from repro.configs import get_config, reduced
    from repro.configs.base import OptimizerConfig
    from repro.launch.steps import make_train_step
    from repro.models import build_model
    from repro.optim import adamw_init

    cfg = dataclasses.replace(reduced(get_config("minicpm-2b")), dtype="float32")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = {"params": params, "opt": jax.eval_shape(adamw_init, params)}
    tokens = jax.ShapeDtypeStruct((2, 16), jax.numpy.int32)
    step = make_train_step(model, OptimizerConfig(lr=1e-3, warmup_steps=1))
    text = jax.jit(step).lower(
        state, {"tokens": tokens, "labels": tokens}
    ).compile().as_text()
    scopes = {s for n in _OP_NAME.findall(text) for s in _repro_scopes(n)}
    assert scopes == {"repro.train.fwd_bwd", "repro.train.optimizer"}


# ---------------------------------------------------------------------------
# scopes of the multi-device programs (four virtual devices)


@pytest.fixture(scope="module")
def op_names():
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(_SCRIPT)], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS_JSON:")]
    assert line, proc.stdout[-4000:]
    return json.loads(line[-1][len("RESULTS_JSON:"):])


def _collectives(ops):
    return [
        (op, name) for op, name in ops
        if op in ("all-reduce", "reduce-scatter", "all-gather",
                  "collective-permute", "all-to-all")
    ]


def test_nap_inter_step_and_intra_reduces_are_scoped(op_names):
    colls = _collectives(op_names["nap"])
    permutes = [n for op, n in colls if op == "collective-permute"]
    reduces = [n for op, n in colls if op == "all-reduce"]
    assert permutes and reduces
    assert all("/repro.comm.nap/inter/" in n for n in permutes)
    assert all("/repro.comm.nap/intra/" in n for n in reduces)


@pytest.mark.parametrize("program, engine", [
    ("mla", "mla"), ("reduce_scatter", "mla_rs"), ("allgather", "mla_ag"),
])
def test_striped_engines_scope_both_phases(op_names, program, engine):
    colls = _collectives(op_names[program])
    phases = {n.split(f"/repro.comm.{engine}/", 1)[1].split("/")[0]
              for _, n in colls}
    assert phases == {"intra", "inter"}


def test_dp_train_step_scopes_its_four_parts_and_each_bucket(op_names):
    scopes = [_repro_scopes(n) for _, n in op_names["train_step"]]
    parts = {s[0] for s in scopes if s}
    assert parts == {"repro.train.fwd_bwd", "repro.train.grad_sync",
                     "repro.train.loss_sync", "repro.train.optimizer"}
    buckets = {s[1] for s in scopes
               if s[:1] == ["repro.train.grad_sync"] and len(s) > 1}
    assert "repro.grad_sync.bucket0" in buckets
    assert all(re.fullmatch(r"repro\.grad_sync\.bucket\d+", b) for b in buckets)
    # the loss goes through NAP, inter-node step and all
    loss = [n for op, n in op_names["train_step"] if op == "collective-permute"
            and "repro.train.loss_sync/" in n]
    assert loss and all("/repro.comm.nap/inter/" in n for n in loss)
