"""Offline batch serving through ``ServeEngine``: a closed backlog.

Set-up makes the weights from the seed on the device, builds the engine
(the harness's clock is the engine's clock), queues the whole backlog
and runs the engine's first two steps: the first admits the first block
of requests into every slot, which prefills every bucket the traffic
uses once; the second calls the decode slice a second time, which
compiles its second form.  The window then runs ``engine.step()`` with
every slot busy and the queue never empty.

``correct``: once the window has closed and the engine is freed, a
sample of the finished requests drawn from the seed, the longest among
them, is run through the plain float32 reference, prompt and served
tokens together; the number compared is the widest gap by which a
served token's logit lies below the reference's best at its position.
"""

from __future__ import annotations

import math
import time

import numpy as np

CHECK = "served_logit_gap"


def setup(cell, seed, spans, log):
    import jax

    from repro.models import build_model
    from repro.serve import PromptBuckets, ServeEngine

    gen = cell.module("traffic", "gen")
    ref = cell.module("reference", cell.config["reference"])
    lm = cell.module("drivers", "lm_common")
    cfg, wl, mix = cell.config, cell.workload, cell.traffic
    layers = int(wl["layers"])
    model = build_model(lm.program_config(cfg, layers))
    seed32 = gen.jax_seed(seed)
    build, key = ref.make_weights(seed32, cfg, layers)
    params = jax.jit(lambda k: lm.to_program(build(k)))(key)
    engine = ServeEngine(
        model, params, num_slots=int(wl["slots"]), max_len=int(wl["max_len"]),
        buckets=PromptBuckets(wl["buckets"]), slice_len=int(wl["slice_len"]),
        clock=time.perf_counter,
    )
    del params
    backlog = gen.requests(mix, seed, int(mix["backlog_blocks"]), cfg["vocab_size"])
    for prompt, n_out in backlog:
        engine.submit(prompt, n_out)
    t0 = time.perf_counter()
    engine.step()
    engine.step()
    log(f"fill and warm-up {time.perf_counter() - t0:.2f}s; slots {wl['slots']} x {wl['max_len']}, "
        f"layers {layers}, backlog {len(backlog)} requests")
    return {"engine": engine, "cell": cell, "seed": seed, "seed32": seed32, "layers": layers,
            "gen": gen, "ref": ref}


def window(s, win):
    """Engine steps until the window has emitted its fixed work:
    ``--seconds`` x the mix's ``tokens_per_window_second`` output
    tokens, at about the engine's rate, so the window lasts about
    ``--seconds``.  It ends at the end of the step that emits the last
    of them.  The schedule is the backlog's own (fixed order, no EOS),
    so every run does the same steps whatever its speed, and the rate,
    those tokens over the whole window, moves with any step's time."""
    eng = s["engine"]
    sched = eng.scheduler
    reqs = sched.requests.values()
    work = math.ceil(win.seconds * float(s["cell"].traffic["tokens_per_window_second"]))
    start = emitted = sum(len(r.token_times) for r in reqs)
    t0 = win.open()
    while emitted - start < work:
        admits = bool(sched.queue) and any(r is None for r in sched.slots)
        before = eng.n_decode_steps
        with win.spans("serve.admit_prefill_slice" if admits else "serve.slice"):
            eng.step()
        win.count("decode_steps", eng.n_decode_steps - before)
        win.done()
        emitted = sum(len(r.token_times) for r in reqs)
    secs = win.close()
    tokens, gaps, touched = 0, [], 0
    for r in reqs:
        ts = [t for t in r.token_times if t >= t0]
        tokens += len(ts)
        touched += bool(ts)
        gaps.extend(np.diff(ts).tolist())
    return {
        "attempted": touched, "failed": 0,
        "metrics": {
            "serve_tokens_per_s": tokens / secs,
            "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3,
        },
        "window_s": secs, "tokens": tokens, "gaps": len(gaps), "queued": len(sched.queue),
    }


def sample(s) -> list:
    """Finished requests to check: the longest, then others drawn from
    the seed until ``sample_tokens`` served tokens are covered."""
    done = [r for r in s["engine"].scheduler.requests.values() if r.done and r.generated]
    done.sort(key=lambda r: r.rid)
    order = s["gen"].rng(s["seed"], 5).permutation(len(done)).tolist()
    longest = max(range(len(done)), key=lambda i: len(done[i].prompt) + len(done[i].generated))
    picked, n = [], 0
    for i in [longest] + [i for i in order if i != longest]:
        picked.append((list(done[i].prompt), list(done[i].generated)))
        n += len(done[i].generated)
        if n >= int(s["cell"].workload["sample_tokens"]):
            break
    return picked


def reference_logits(s, picked, precision="f32"):
    """Reference logits at every served position of ``picked``, one
    request at a time, padded to the cache length (one program)."""
    import jax
    import jax.numpy as jnp

    cfg, wl, ref = s["cell"].config, s["cell"].workload, s["ref"]
    L, R = int(wl["max_len"]), max(len(g) for _, g in picked)
    build, key = ref.make_weights(s["seed32"], cfg, s["layers"])
    w = jax.jit(build)(key)
    fn = jax.jit(lambda w, t, rows: ref.logits_at(w, t, rows, cfg, precision))
    out = []
    for prompt, gen_toks in picked:
        seq = (prompt + gen_toks[:-1] + [0] * L)[:L]
        rows = [len(prompt) - 1 + i for i in range(len(gen_toks))]
        rows += [rows[-1]] * (R - len(rows))
        lg = fn(w, jnp.asarray(seq, jnp.int32), jnp.asarray(rows, jnp.int32))
        out.append(np.asarray(lg[: len(gen_toks)]))
    return out


def served_gap(logits, picked) -> float:
    """Widest gap below the reference's best logit of a served token."""
    return float(max((lg.max(axis=1) - lg[np.arange(len(g)), g]).max()
                     for lg, (_, g) in zip(logits, picked)))


def judge(s, picked, tokens=None) -> list[dict]:
    """The comparison that decides ``correct``: the float32 reference
    over each sampled request's prompt and served tokens, and the widest
    gap below its best logit of ``tokens`` at those positions (the
    served tokens themselves by default), beside its limit."""
    gap = served_gap(reference_logits(s, picked), picked if tokens is None else tokens)
    limit = s["cell"].workload["limits"][CHECK]
    return [{"name": CHECK, "value": gap, "limit": limit, "ok": gap <= limit}]


def check(s, measured, log):
    import gc

    if not measured["queued"]:
        log("the backlog ran dry in the window: slots waited for work")
    picked = sample(s)
    log(f"sampled {len(picked)} requests, {sum(len(g) for _, g in picked)} served tokens, "
        f"longest {max(len(p) + len(g) for p, g in picked)} positions")
    s.pop("engine")
    gc.collect()
    return judge(s, picked)


def control(cell, seed, log):
    """The reference in the program's place in float8_e4m3, judged as
    a run is: at every served position of a sample of a short run, the
    token the float8 reference puts first."""
    import gc

    s = setup(cell, seed, None, log)
    reqs = s["engine"].scheduler.requests.values()
    while sum(len(r.generated) for r in reqs if r.done) < int(cell.workload["sample_tokens"]):
        s["engine"].step()
    picked = sample(s)
    s.pop("engine")
    gc.collect()
    fp8 = reference_logits(s, picked, "fp8")
    return judge(s, picked, [(p, lg.argmax(axis=1).tolist()) for (p, _), lg in zip(picked, fp8)])


def _slice_fault(alter):
    from repro.serve import decode

    def wrap(orig):
        def make(*a, **k):
            f = orig(*a, **k)
            return lambda params, cache, tok, active: alter(f(params, cache, tok, active), cache)
        return make

    return decode, "make_decode_slice", wrap


def _fault_altered():
    # every recorded token is changed where the slice produces it
    return _slice_fault(lambda r, cache: (r[0] + 1, *r[1:]))


def _fault_stale():
    # the slice hands back the cache it was given, unchanged
    return _slice_fault(lambda r, cache: (r[0], r[1], cache, r[3]))


# planted faults: (owner, attribute, wrapper of the original)
FAULTS = {"altered": _fault_altered, "stale": _fault_stale}
