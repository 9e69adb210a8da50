"""Serving load benchmark: offered QPS -> throughput / latency (BENCH_9).

Two halves, split so the CI gate is deterministic:

1. **Measurement** — a real meshed :class:`repro.serve.ServeEngine` on
   the virtual 2x4 CPU grid decodes with every slot busy at two slot
   widths, recording per-decode-step wall-clock as
   ``MachineParams.fit``-shaped ``(size_bytes, seconds, senders)`` rows
   (the logits-allreduce payload is the size axis; effective
   single-message rows, senders=1).  The rows feed a
   ``MachineParams.fit`` self-check — open item 4's recalibration loop
   eating real serving data.

2. **Load curve** — a deterministic discrete-event simulation drives
   the *real* :class:`Scheduler` + :class:`Router` classes (admission,
   slots, FIFO, outstanding-token routing, straggler rerouting) with
   the measured per-step time as the service clock, sweeping offered
   QPS to saturation.  Simulated time keeps the CI assertion — tokens/s
   monotone non-decreasing in offered QPS below saturation — exact
   rather than wall-clock flaky, while every control-plane decision is
   made by the production code under test.

The dispatch table reports the (engine, chunks) decision for each
decode-step collective on the executed grid and on representative
production grids; the gate asserts the per-token logits allreduce lands
on the latency-regime NAP engine for every multi-node grid.

Usage:
  python benchmarks/serve_load.py --json reports/BENCH_9.json [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import deque
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
for p in (str(_ROOT), str(_ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# virtual devices exist only on the CPU backend: never time a chip here
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import numpy as np  # noqa: E402


# ---------------------------------------------------------------------------
# 1. measurement: real engine per-decode-step wall-clock
# ---------------------------------------------------------------------------


def measure_engine(num_slots: int, *, slices: int, gen_len: int):
    """Decode with every slot busy on the 2x4 grid; returns (fit rows,
    median seconds per decode step, dispatch report)."""
    import jax

    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.serve import PromptBuckets, ServeEngine

    cfg = reduced(get_config("minicpm-2b"))
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    mesh = make_mesh((2, 4), ("pod", "data"))
    engine = ServeEngine(
        model, params, num_slots=num_slots, max_len=32,
        buckets=PromptBuckets([8]), mesh=mesh,
    )
    rng = np.random.default_rng(0)
    for _ in range(num_slots):  # saturate every slot
        engine.submit(
            rng.integers(0, cfg.vocab_size, size=6).tolist(),
            max_new_tokens=gen_len,
        )
    warm = engine.step()  # admission + first slice: compile, not timed
    assert not warm
    engine.step_times.clear()
    for _ in range(slices):
        engine.step()
    rows = engine.fit_rows()
    per_step = float(np.median([t for _, t, _ in rows])) if rows else 0.0
    return rows, per_step, engine.dispatch_report()


def fit_self_check(rows) -> dict:
    """Feed the measured rows to ``MachineParams.fit`` (>= 2 k==1 rows at
    distinct sizes required) and sanity-check the constants."""
    from repro.core.perf_model import MachineParams

    fitted = MachineParams.fit(rows, name="serve_fit")
    ok = (
        np.isfinite(fitted.alpha)
        and np.isfinite(fitted.R_b)
        and fitted.alpha >= 0
        and fitted.R_b > 0
    )
    return {
        "ok": bool(ok),
        "alpha_s": float(fitted.alpha),
        "R_b_bytes_per_s": float(fitted.R_b),
        "n_rows": len(rows),
    }


# ---------------------------------------------------------------------------
# 2. deterministic load simulation over the real Scheduler + Router
# ---------------------------------------------------------------------------


class SimReplica:
    """A serving replica for the discrete-event load model: the *real*
    :class:`repro.serve.Scheduler` drives slots/admission/FIFO; only the
    device slice is simulated (one decode step = ``tau`` simulated
    seconds, prefill = ``bucket_len * tau``), using the wall-clock
    measured on the real engine."""

    def __init__(self, num_slots: int, tau: float, *, max_queue=None):
        from repro.serve import PromptBuckets, Scheduler

        self.scheduler = Scheduler(
            num_slots, max_queue=max_queue, buckets=PromptBuckets([8, 16])
        )
        self.tau = tau
        self.slow = 1.0  # straggler injection multiplier
        self.clock = 0.0
        self.steps = 0

    # Router surface -------------------------------------------------------
    def submit(self, prompt, max_new_tokens, *, arrival=0.0, extras=None):
        self.clock = max(self.clock, arrival)
        return self.scheduler.submit(
            prompt, max_new_tokens, arrival=arrival, extras=extras
        )

    def outstanding_tokens(self) -> int:
        return self.scheduler.outstanding_tokens()

    @property
    def idle(self) -> bool:
        return self.scheduler.idle

    # simulation -----------------------------------------------------------
    def step(self) -> float:
        """One decode-step boundary; returns the *decode* wall-clock
        (what the real engine's slice timing covers — prefill for the
        admitted requests advances the clock but is not the straggler
        signal, mirroring ``ServeEngine.step``)."""
        admitted = self.scheduler.admit(now=self.clock)
        decode_dt = self.tau * self.slow
        dt = decode_dt
        for req in admitted:  # sequential B=1 bucketed prefill
            dt += req.bucket_len * self.tau * self.slow
        self.clock += dt
        for slot, req in enumerate(self.scheduler.slots):
            if req is not None:
                self.scheduler.record_token(slot, 1, now=self.clock)
        self.steps += 1
        return decode_dt


def run_load_point(
    offered_qps: float,
    *,
    tau: float,
    n_requests: int,
    n_replicas: int,
    num_slots: int,
    prompt_len: int,
    gen_len: int,
    straggle_at: int | None = None,
) -> dict:
    """One point of the QPS curve: deterministic arrivals at
    ``offered_qps`` through the real Router into SimReplicas."""
    from repro.serve import Router

    replicas = [SimReplica(num_slots, tau) for _ in range(n_replicas)]
    router = Router(replicas, straggler_threshold=2.0, recovery=3)
    arrivals = [i / offered_qps for i in range(n_requests)]
    prompt = list(range(1, prompt_len + 1))

    pending = deque(arrivals)
    submitted = []
    while pending or not router.idle:
        busy = [i for i, r in enumerate(replicas) if not r.idle]
        t_dec = min((replicas[i].clock for i in busy), default=np.inf)
        if pending and pending[0] <= t_dec:
            t_arr = pending.popleft()
            submitted.append(
                router.submit(prompt, gen_len, arrival=t_arr)
            )
            continue
        i = min(busy, key=lambda r: replicas[r].clock)
        rep = replicas[i]
        if straggle_at is not None and rep.steps == straggle_at and i == 0:
            rep.slow = 5.0  # inject a straggler on replica 0
        dt = rep.step()
        router.observe_step(i, rep.steps, dt)
        if rep.slow > 1.0 and rep.steps > (straggle_at or 0) + 4:
            rep.slow = 1.0  # stall clears; health recovers after N clean

    done = [r for r in submitted if r.state == "finished"]
    assert len(done) == n_requests, "simulation lost requests"
    total_tokens = sum(len(r.generated) for r in done)
    t_end = max(r.token_times[-1] for r in done)
    makespan = t_end - arrivals[0]
    gaps = []
    for r in done:
        prev = r.arrival
        for t in r.token_times:
            gaps.append(t - prev)
            prev = t
    gaps = np.asarray(gaps)
    return {
        "offered_qps": float(offered_qps),
        "sim_tokens_per_s": float(total_tokens / makespan),
        "p50_token_latency_s": float(np.percentile(gaps, 50)),
        "p99_token_latency_s": float(np.percentile(gaps, 99)),
        "completed": len(done),
        "makespan_s": float(makespan),
        "rerouted": router.n_rerouted,
        "degraded_episodes": sum(h.n_degraded for h in router.health),
    }


# ---------------------------------------------------------------------------
# dispatch table
# ---------------------------------------------------------------------------


def dispatch_grids(vocab: int, d_model: int, b_max: int) -> dict:
    """Model-driven dispatch for the decode collectives on production
    grids (host-side: ``Topology.of`` needs no device axes)."""
    from repro.core import comm

    out = {}
    # per-replica TP serving grids (scale beyond these is the Router's
    # data-parallel job, not a wider tensor-parallel group)
    for n, ppn in [(1, 8), (2, 4), (2, 8), (4, 8), (8, 8)]:
        topo = comm.Topology.of(n, ppn)
        ctx = comm.CommContext(topo)
        group = topo.group
        rows = group * b_max
        d_cols = -(-d_model // group)
        grid = {}
        for name, (nbytes, op, coll, pin) in {
            "logits_allreduce": (rows * vocab * 4, "sum", "allreduce", None),
            "hidden_allgather": (
                rows * d_cols * group * 4, "sum", "allgather",
                "mla_ag" if topo.has_slow_domain else None,
            ),
            "eos_min_reduce": (4, "min", "allreduce", "psum"),
        }.items():
            d = ctx.dispatch(int(nbytes), op, collective=coll, algorithm=pin)
            grid[name] = {
                "nbytes": int(nbytes),
                "engine": d.engine,
                "chunks": d.chunks,
            }
        out[f"{n}x{ppn}"] = grid
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    slices = 6 if args.quick else 20
    gen_len = 8 if args.quick else 16

    # 1. real-engine measurement at two slot widths (two payload sizes:
    # MachineParams.fit needs >= 2 distinct k==1 sizes)
    fit_rows = []
    per_step = {}
    dispatch_executed = None
    for num_slots in (8, 16):
        rows, tau, disp = measure_engine(
            num_slots, slices=slices, gen_len=max(gen_len, slices + 2)
        )
        fit_rows.extend(rows)
        per_step[str(num_slots)] = tau
        if dispatch_executed is None:
            dispatch_executed = disp
    fit = fit_self_check(fit_rows)
    import jax

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "count": jax.device_count(),
    }

    # 2. QPS sweep through the real Scheduler/Router (simulated clock)
    tau = per_step["8"]
    n_replicas, num_slots = 2, 8
    prompt_len, sim_gen = 6, 16
    # tokens/s capacity ~ n_replicas * num_slots / tau; saturating QPS
    # ~ capacity / tokens-per-request — sweep from 1/8x to 4x that
    qps_sat = (n_replicas * num_slots / tau) / (sim_gen + prompt_len)
    multipliers = (
        [0.25, 1.0, 4.0] if args.quick
        else [0.125, 0.25, 0.5, 1.0, 2.0, 4.0]
    )
    n_requests = 24 if args.quick else 96
    curve = [
        run_load_point(
            m * qps_sat, tau=tau, n_requests=n_requests,
            n_replicas=n_replicas, num_slots=num_slots,
            prompt_len=prompt_len, gen_len=sim_gen,
        )
        for m in multipliers
    ]
    # straggler scenario: same load, slowdown injected on replica 0
    straggler = run_load_point(
        qps_sat, tau=tau, n_requests=n_requests,
        n_replicas=n_replicas, num_slots=num_slots,
        prompt_len=prompt_len, gen_len=sim_gen, straggle_at=4,
    )

    # 3. dispatch decisions per decode collective across grids
    from repro.configs import get_config, reduced

    cfg = reduced(get_config("minicpm-2b"))
    grids = dispatch_grids(cfg.vocab_size, cfg.d_model, b_max=1)

    # -- checks (the CI gate) ----------------------------------------------
    checks = {}
    # tokens/s monotone non-decreasing in offered QPS up to the peak
    tput = [pt["sim_tokens_per_s"] for pt in curve]
    peak = int(np.argmax(tput))
    checks["monotone_below_saturation"] = bool(
        all(tput[i + 1] >= tput[i] * (1 - 1e-9) for i in range(peak))
    )
    # the per-token logits allreduce rides NAP on every multi-node grid
    checks["nap_on_multinode"] = all(
        g["logits_allreduce"]["engine"] == "nap"
        for key, g in grids.items()
        if not key.startswith("1x")
    )
    checks["nap_executed_grid"] = (
        dispatch_executed["logits_allreduce"]["engine"] == "nap"
    )
    checks["fit_ok"] = fit["ok"]
    checks["straggler_rerouted"] = straggler["rerouted"] > 0

    report = {
        "bench": "serve_load",
        "quick": bool(args.quick),
        "device": device,
        "measured": {
            "grid": "2x4",
            "fit_rows": [[int(s), float(t), int(k)] for s, t, k in fit_rows],
            "cpu_per_step_s": per_step,
            "machine_params_fit": fit,
        },
        "dispatch": {"executed_2x4": dispatch_executed, "grids": grids},
        "load": {
            "n_replicas": n_replicas,
            "num_slots": num_slots,
            "prompt_len": prompt_len,
            "gen_len": sim_gen,
            "saturation_qps_model": float(qps_sat),
            "curve": curve,
            "straggler_scenario": straggler,
        },
        "checks": checks,
    }

    if args.json:
        out = Path(args.json)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=2))
        print(f"wrote {out}")

    failures = sum(1 for ok in checks.values() if not ok)
    for name, ok in checks.items():
        print(f"check {name}: {'ok' if ok else 'FAIL'}")
    print(
        f"simulated qps curve on {device['platform']} step times: "
        f"{[round(pt['sim_tokens_per_s'], 1) for pt in curve]} tok/s "
        f"at {[round(pt['offered_qps'], 2) for pt in curve]} qps"
    )
    return failures


if __name__ == "__main__":
    sys.exit(main())
