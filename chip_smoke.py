#!/usr/bin/env python3
"""Drive the main path once on a TPU and check what comes out.

Default (one chip): ``minicpm-2b`` at its published widths (d_model
2304, 36 heads, d_ff 5760, vocab 122,753, bf16), weights random from
``--seed``.

* **serve** — a :class:`repro.serve.ServeEngine` with all 40 layers, 8
  slots and 1024-token caches serves prompts of two padded buckets, one
  request joining in flight.  Its tokens must equal a serial
  one-at-a-time run through the same engine, and be greedy choices of
  the plain decode step that :func:`repro.launch.serve.serve_batch`
  runs (see ``GREEDY_MARGIN_TOL``).
* **train** — :func:`repro.launch.steps.make_dp_train_step` on a
  one-chip ``("pod", "data")`` mesh, depth cut to fit one chip, 4 steps
  on one fixed batch, once uncompressed and once with int8 transport
  (the Pallas transport kernels, compiled: ``tpu_custom_call`` must be
  in the step's HLO).  Step 0 must sit within 0.5 of ln(vocab) and the
  loss must fall over the steps that update.

``--chips 4`` (one v5e:2x2 host) runs only what exists across chips:
the allreduce engines against a NumPy sum, NAP-synced training against
``psum``, int8 and int4+error-feedback transport, the meshed serving
engine against a serial run on the same mesh, and where the state lands.

Every phase raises on failure.  Without a TPU the script exits non-zero
before any phase.  The last line of stdout is one JSON object naming the
device.  The phase functions take a config and run anywhere, so the
tests rehearse them on the CPU at a reduced size.

Usage:
  python chip_smoke.py [--seed N]
  python chip_smoke.py --chips 4 [--seed N]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import OptimizerConfig  # noqa: E402
from repro.core import comm  # noqa: E402
from repro.data import SyntheticLM  # noqa: E402
from repro.launch.mesh import make_mesh, mesh_topology  # noqa: E402
from repro.launch.serve import serve_batch  # noqa: E402
from repro.launch.steps import init_dp_train_state, make_dp_train_step  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime import enable_compile_cache  # noqa: E402
from repro.serve import PromptBuckets, ServeEngine  # noqa: E402

ARCH = "minicpm-2b"
# replicated params + Adam moments are ~12 B/param: 8 of the 40 layers
# (771M params with the 122,753 x 2304 embedding) fill ~11 GB of 16.
# Error-feedback residuals add 4 B/param per chip, so the four-chip
# run, which trains with them, keeps 4 layers (527M params): its
# int4+EF step compiled for a described v5e:2x2 needs 6.87 GiB of
# arguments and 5.00 GiB of temporaries per chip (6 layers: 8.46 +
# 5.91 GiB, too close to the 16 GiB chip).
TRAIN_LAYERS = 8
TRAIN_LAYERS_4CHIP = 4
MESH_AXES = ("pod", "data")


# bf16 greedy decode through two different programs (the engine's
# slot-vmapped slice, serve_batch's batched step) rounds differently and
# can flip near-tied argmaxes.  Each engine token must be a greedy
# choice of the plain decode step's logits to within this many logit
# units: 8 bf16 ulps at 1.0 (the ulp there is 2^-7).  Random minicpm-2b
# weights give logits of standard deviation ~1, so a token that is not
# among the near-ties falls short of the max by ~4.  The serve phase
# plants two faults (tokens one step late; tokens teacher-forced under
# another request's prompt) and requires both to exceed this bound.
GREEDY_MARGIN_TOL = 2.0**-4

# train: Adam's first updates are sign-like, and with bf16 weights an
# update below half an ulp is lost.  At lr 1e-4 every weight near the
# 0.02 init scale moves by a whole ulp (~1.2e-4) at once and the loss
# rose 1.5 nats in one step on the chip; at 2e-5 only the smaller
# weights move, by about lr, which is a descent step.
TRAIN_LR = 2e-5


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def peak_bytes() -> int | None:
    """Peak device memory of device 0, where the backend reports it."""
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else int(stats["peak_bytes_in_use"])


def live_bytes() -> int:
    """Bytes of device arrays still alive in this process."""
    return sum(x.nbytes for x in jax.live_arrays())


def require_released(phase: str) -> None:
    """A finished phase holds no device memory: what it leaves alive
    (the serving engine's params and cache are ~8 GB at full depth)
    is missing from the next phase's budget.  The phases share no
    compiled program, so their executables are dropped too."""
    jax.clear_caches()
    held = live_bytes()
    stats = jax.devices()[0].memory_stats() or {}
    log(f"after {phase}: live array bytes {held}, "
        f"device bytes_in_use {stats.get('bytes_in_use')}")
    require(held == 0, f"{phase} left {held} bytes of device arrays alive")


def _gaps(logits, ids):
    """How far below the max logit each of ``ids`` falls, plus the
    top-1 minus top-2 and top-1 minus median gaps (the logit spread)."""
    row = logits[0, -1].astype(jnp.float32)
    top2 = jax.lax.top_k(row, 2)[0]
    return top2[0] - row[ids], top2[0] - top2[1], top2[0] - jnp.median(row)


def greedy_margins(model, params, prompt, tokens, max_len: int) -> dict:
    """Teacher-force ``prompt + tokens`` through the plain decode step
    (the one :func:`serve_batch` runs).  For each generated token, how
    far its logit falls below the step's max logit (0 where it is the
    argmax): ``own``; the same for the next token, i.e. tokens emitted
    one step late (``late``, a planted fault); and the logit spread."""
    step = jax.jit(model.decode_step)
    gaps = jax.jit(_gaps)
    cache = model.init_decode(params, 1, max_len=max_len)
    seq = list(prompt) + list(tokens[:-1])
    rows = []
    for t, tok in enumerate(seq):
        logits, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32))
        g = t - len(prompt) + 1  # index of the token this step emits
        if g >= 0:
            ids = jnp.asarray([tokens[g], tokens[min(g + 1, len(tokens) - 1)]])
            rows.append(jax.device_get(gaps(logits, ids)))
    return {
        "own": [float(r[0][0]) for r in rows],
        "late": [float(r[0][1]) for r in rows[:-1]],
        "top1_top2": [float(r[1]) for r in rows],
        "top1_median": [float(r[2]) for r in rows],
    }


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def serve_phase(
    cfg,
    *,
    seed: int,
    num_slots: int = 8,
    max_len: int = 1024,
    gen_len: int = 32,
    prompt_lens=(40, 100, 56, 120, 72),
    buckets=(64, 128),
    mesh=None,
) -> dict:
    """Serve ``prompt_lens`` prompts serially and then continuously
    batched (the last joins in flight) through one engine; without a
    mesh also through :func:`serve_batch`.  Raises unless continuous and
    serial tokens are identical and, without a mesh, every engine token
    is a greedy choice of the plain decode step within
    ``GREEDY_MARGIN_TOL`` while both planted faults exceed it."""
    model = build_model(cfg)
    init = jax.jit(
        model.init,
        out_shardings=None if mesh is None else NamedSharding(mesh, P()),
    )
    params = init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=n).tolist() for n in prompt_lens
    ]
    engine = ServeEngine(
        model, params, num_slots=num_slots, max_len=max_len,
        buckets=PromptBuckets(buckets), mesh=mesh,
    )

    t0 = time.perf_counter()
    serial = []
    for p in prompts:
        req = engine.submit(p, gen_len)
        serial.append(engine.run()[req.rid])
    t_serial = time.perf_counter() - t0

    t0 = time.perf_counter()
    reqs = [engine.submit(p, gen_len) for p in prompts[:-1]]
    engine.step()
    reqs.append(engine.submit(prompts[-1], gen_len))  # joins in flight
    out = engine.run()
    t_cont = time.perf_counter() - t0
    require(engine.idle, "engine not idle after run()")
    cont = [out[r.rid] for r in reqs]
    require(
        all(len(t) == gen_len for t in cont),
        f"a request did not finish: {[len(t) for t in cont]}",
    )

    result = {
        "prompt_lens": list(prompt_lens),
        "buckets": list(buckets),
        "num_slots": num_slots,
        "max_len": max_len,
        "gen_len": gen_len,
        "serial_s": t_serial,
        "continuous_s": t_cont,
        "continuous_eq_serial": cont == serial,
        # host clock per decode step of the slices run (first compiles)
        "decode_step_s_median": float(
            np.median([t for _, t, _ in engine.fit_rows()[1:]])
        ),
        "dispatch": engine.dispatch_report(),
        "params_devices": len(
            jax.tree.leaves(engine.params)[0].sharding.device_set
        ),
    }
    # the references below hold their own caches: free the engine's
    # (2.8 GiB at 8 x 1024) so the phase peaks below the chip's 16 GiB
    del engine
    if mesh is None:
        t0 = time.perf_counter()
        ref = [
            np.asarray(
                serve_batch(
                    model, params, jnp.asarray([p], jnp.int32),
                    gen_len=gen_len, max_len=max_len,
                )
            )[0].tolist()
            for p in prompts
        ]
        result["serve_batch_s"] = time.perf_counter() - t0
        result["serial_eq_serve_batch"] = serial == ref
        result["mismatch_at"] = [
            next((i for i, (a, b) in enumerate(zip(s, r)) if a != b), None)
            for s, r in zip(serial, ref)
        ]
        margins = [
            greedy_margins(model, params, p, toks, max_len)
            for p, toks in zip(prompts, serial)
        ]
        # planted fault: each request's tokens under the next prompt
        # (cache rows crossed between slots)
        swapped = [
            greedy_margins(model, params, p, toks, max_len)["own"]
            for p, toks in zip(prompts[1:] + prompts[:1], serial)
        ]
        own = [x for m in margins for x in m["own"]]
        spread = {
            k: np.percentile([x for m in margins for x in m[k]],
                             [0, 50, 100]).tolist()
            for k in ("top1_top2", "top1_median")
        }
        result["max_greedy_margin"] = max(own)
        result["greedy_margins_nonzero"] = sorted(x for x in own if x > 0)
        result["logit_spread_min_med_max"] = spread
        # a fault is caught when some token of the request exceeds the
        # bound; the weakest request is the one that counts
        result["fault_margin"] = {
            "late": min(max(m["late"]) for m in margins),
            "swapped": min(max(m) for m in swapped),
        }
    log(f"serve: {json.dumps(result)}")
    require(result["continuous_eq_serial"], "continuous != serial tokens")
    if mesh is None:
        require(
            result["max_greedy_margin"] <= GREEDY_MARGIN_TOL,
            "engine tokens are not greedy under the plain decode step: "
            f"margin {result['max_greedy_margin']} > {GREEDY_MARGIN_TOL} "
            f"(first mismatch with serve_batch: {result['mismatch_at']})",
        )
        for fault, m in result["fault_margin"].items():
            require(
                m > GREEDY_MARGIN_TOL,
                f"the margin check cannot see a planted fault ({fault}): "
                f"{m} <= {GREEDY_MARGIN_TOL}",
            )
    return result


def train_phase(
    cfg,
    *,
    mesh,
    seed: int,
    batch: int,
    seq: int,
    steps: int = 4,
    sync_cfg: comm.CommPolicy = comm.CommPolicy(),
) -> dict:
    """``steps`` steps of the explicit-collective DP train step on
    ``mesh`` with ``batch`` sequences per chip, all on one batch.  The
    warmup starts at lr 0, so step 0 updates nothing and every later
    step does.  Raises unless the losses are finite, step 0 sits within
    0.5 of ln(vocab), and the last loss is below step 1's (the loss
    before the first update).  Not at every step: on the chip the first
    update took 2.57 nats off and the next put 0.02 back (Adam's
    sign-like early steps overshoot on one fixed batch)."""
    opt_cfg = OptimizerConfig(
        lr=TRAIN_LR, schedule="constant", warmup_steps=1
    )
    topo = mesh_topology(mesh)
    state = init_dp_train_state(
        cfg, opt_cfg, mesh, sync_cfg, jax.random.PRNGKey(seed)
    )
    data = SyntheticLM(
        vocab_size=cfg.vocab_size, seq_len=seq,
        global_batch=batch * topo.group, seed=seed, mesh=mesh,
        batch_axes=topo.axes,
    )
    step = jax.jit(
        make_dp_train_step(cfg, opt_cfg, mesh, sync_cfg), donate_argnums=(0,)
    )
    b = data.batch(0)
    t0 = time.perf_counter()
    compiled = step.lower(state, b).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()

    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, b)
        losses.append(float(metrics["loss"]))  # waits for the step
        step_s.append(time.perf_counter() - t0)
    devices = {
        len(x.sharding.device_set) for x in jax.tree.leaves(state)
    }
    del state
    result = {
        "layers": cfg.num_layers,
        "batch_per_chip": batch,
        "seq": seq,
        "algorithm": sync_cfg.algorithm,
        "compress_bits": sync_cfg.compress_bits,
        "error_feedback": sync_cfg.error_feedback,
        "compile_s": compile_s,
        "step_s": step_s,
        "losses": losses,
        "tpu_custom_calls": hlo.count("tpu_custom_call"),
        "state_devices": sorted(devices),
        "peak_bytes_in_use": peak_bytes(),
    }
    log(f"train: {json.dumps(result)}")
    require(all(math.isfinite(l) for l in losses), f"loss not finite: {losses}")
    ln_v = math.log(cfg.vocab_size)
    require(
        abs(losses[0] - ln_v) < 0.5,
        f"step-0 loss {losses[0]} not within 0.5 of ln(V)={ln_v:.3f}",
    )
    require(
        losses[-1] < losses[1],
        f"loss does not fall over the updating steps: {losses}",
    )
    require(devices == {mesh.size}, f"state not on all {mesh.size} devices")
    return result


def loss_drop(result: dict) -> float:
    """How far the loss fell over the steps that updated the weights."""
    return result["losses"][1] - result["losses"][-1]


def require_compressed_close(ref: dict, got: dict, name: str) -> None:
    """A compressed run starts from the same loss as ``ref`` and its loss
    falls by ``ref``'s drop to within half of it."""
    require(
        got["losses"][0] == ref["losses"][0],
        f"{name}: step-0 loss {got['losses'][0]} != {ref['losses'][0]}",
    )
    d_ref, d_got = loss_drop(ref), loss_drop(got)
    log(f"{name}: loss drop {d_got} vs uncompressed {d_ref}")
    require(
        abs(d_got - d_ref) <= 0.5 * d_ref,
        f"{name}: loss drop {d_got} not within half of {d_ref}",
    )


def allreduce_phase(
    mesh,
    *,
    seed: int,
    engines=("nap", "mla", "mla_pipelined", "psum"),
    sizes=(8, 64 << 10, 4 << 20),
) -> list[dict]:
    """Every pinned allreduce engine at every f32 payload size (bytes per
    chip) against a NumPy sum of the per-chip inputs."""
    topo = mesh_topology(mesh)
    ctx = comm.CommContext(topo)
    spec = P(topo.axes)
    rng = np.random.default_rng(seed)
    rows = []
    for nbytes in sizes:
        n = max(1, nbytes // 4)
        x = rng.standard_normal((topo.group, n)).astype(np.float32)
        want = x.sum(axis=0, dtype=np.float64)
        xs = jax.device_put(x, NamedSharding(mesh, spec))
        for eng in engines:
            fn = jax.jit(
                jax.shard_map(
                    lambda v, eng=eng: ctx.allreduce(v, algorithm=eng),
                    mesh=mesh, in_specs=spec, out_specs=spec,
                )
            )
            got = np.asarray(fn(xs))
            err = float(np.abs(got - want[None]).max())
            # f32 sums of `group` unit normals: a few ulps of the sum
            tol = 1e-5 * float(np.abs(x).sum(axis=0).max()) + 1e-6
            d = ctx.dispatch(n * 4, algorithm=eng)
            rows.append({
                "engine": eng, "bytes": n * 4, "chunks": d.chunks,
                "max_abs_err": err, "ok": err <= tol,
            })
    log(f"allreduce: {json.dumps(rows)}")
    bad = [r for r in rows if not r["ok"]]
    require(not bad, f"allreduce disagrees with NumPy: {bad}")
    return rows


def four_chip_phases(cfg, mesh, *, seed: int, train_cfg, batch: int,
                     seq: int, serve_kw: dict) -> dict:
    """The ``--chips 4`` run: collectives, NAP-vs-psum training, int8 and
    int4+EF transport, and meshed serving, all on ``mesh``."""
    out = {"allreduce": allreduce_phase(mesh, seed=seed)}
    require_released("allreduce")
    train = {}
    for name, policy in (
        ("psum", comm.CommPolicy(algorithm="psum")),
        ("nap", comm.CommPolicy(algorithm="nap")),
        ("int8", comm.CommPolicy(algorithm="nap", compress_bits=8)),
        ("int4_ef", comm.CommPolicy(
            algorithm="nap", compress_bits=4, error_feedback=True,
        )),
    ):
        train[name] = train_phase(
            train_cfg, mesh=mesh, seed=seed, batch=batch, seq=seq,
            sync_cfg=policy,
        )
        require_released(f"train {name}")
    a, b = np.array(train["nap"]["losses"]), np.array(train["psum"]["losses"])
    rel = float(np.abs(a - b).max() / np.abs(b).max())
    log(f"nap_vs_psum: max relative loss difference {rel}")
    require(rel < 1e-4, f"NAP losses {a} != psum losses {b}")
    # against NAP, which also reduces the int8 run's loss: psum sums the
    # per-chip losses in another order (1 ulp apart at step 0 on a chip)
    require_compressed_close(train["nap"], train["int8"], "int8")
    out["train"] = train
    out["serve"] = serve_phase(cfg, seed=seed, mesh=mesh, **serve_kw)
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _device() -> dict:
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache = enable_compile_cache()
    device = _device()
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; JAX found {device['platform']}"
        )
    if device["count"] < args.chips:
        raise SystemExit(
            f"--chips {args.chips} but JAX sees {device['count']} devices"
        )
    log(f"device: {json.dumps(device)} compile_cache: {cache}")

    cfg = get_config(ARCH)
    train_cfg = dataclasses.replace(cfg, num_layers=TRAIN_LAYERS)
    log(
        f"config: {ARCH} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"dtype={cfg.dtype}; train layers={TRAIN_LAYERS} (one chip), "
        f"{TRAIN_LAYERS_4CHIP} (four)"
    )
    t_all = time.perf_counter()
    if args.chips == 1:
        t0 = time.perf_counter()
        serve_phase(cfg, seed=args.seed)
        log(f"phase serve: {time.perf_counter() - t0:.1f}s "
            f"peak_bytes_in_use={peak_bytes()}")
        require_released("serve")
        mesh = make_mesh((1, 1), MESH_AXES)
        runs = []
        for policy in (comm.CommPolicy(), comm.CommPolicy(compress_bits=8)):
            t0 = time.perf_counter()
            runs.append(train_phase(
                train_cfg, mesh=mesh, seed=args.seed, batch=4, seq=512,
                sync_cfg=policy,
            ))
            log(f"phase train bits={policy.compress_bits}: "
                f"{time.perf_counter() - t0:.1f}s")
            require_released(f"train bits={policy.compress_bits}")
        require(
            runs[1]["tpu_custom_calls"] > 0,
            "no tpu_custom_call in the compressed step: the transport "
            "kernels did not compile for the chip",
        )
        require_compressed_close(runs[0], runs[1], "int8")
    else:
        mesh = make_mesh((2, 2), MESH_AXES)
        r = four_chip_phases(
            cfg, mesh, seed=args.seed,
            train_cfg=dataclasses.replace(cfg, num_layers=TRAIN_LAYERS_4CHIP),
            batch=4, seq=512, serve_kw={},
        )
        for name in ("int8", "int4_ef"):
            require(
                r["train"][name]["tpu_custom_calls"] > 0,
                f"no tpu_custom_call in the {name} step",
            )
    log(f"total: {time.perf_counter() - t_all:.1f}s "
        f"peak_bytes_in_use={peak_bytes()}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
