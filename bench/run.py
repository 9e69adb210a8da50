#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to a cell is found by name: the cell's entry in
``BENCHMARK.json``, ``bench/workloads/<cell>.json`` (driver, mesh and
settings), ``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``,
``bench/drivers/<driver>.py`` and, with ``--trace 1``,
``bench/metrics/<metric>.py`` for each per-layer metric of the cell.

A run: set-up (weights and state from the seed, every program of the
cell compiled or loaded from the persistent cache, warm-up), then the
window of ``--seconds``, then the comparison with the plain reference
that decides ``correct``.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero before any work and prints no result.
The last stdout line is the JSON result; the last stderr lines are each
compared number beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))  # the system under test
# trace files are reduced and deleted within the run
TRACE_DIR = ROOT / ".bench_traces"


def load_module(path: Path):
    """Import a harness file by path (names may hold ``.`` and ``-``)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    rel = path.resolve().relative_to(BENCH.parent) if path.resolve().is_relative_to(BENCH.parent) else path
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in str(rel.with_suffix("")))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _merge(base: dict, over: dict | None) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


@dataclasses.dataclass
class Cell:
    """Everything one run of one cell needs, read from files."""

    name: str
    chips: int
    config: dict
    workload: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench: Path = BENCH

    def module(self, kind: str, name: str):
        return load_module(self.bench / kind / f"{name}.py")


def load_cell(name: str, *, bench_json: Path | None = None, bench: Path = BENCH,
              overrides: dict | None = None) -> Cell:
    """Read cell ``name``; ``overrides`` (keys ``config``, ``workload``,
    ``traffic``) is merged over the files, for tests at small sizes."""
    spec = json.loads((bench_json or bench.parent / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    o = overrides or {}
    read = lambda kind, n: json.loads((bench / kind / f"{n}.json").read_text())  # noqa: E731
    reported = {m["name"] for m in spec["end_to_end"] if name in m.get("workloads", [name])}
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_merge(read("configs", entry["config"]), o.get("config")),
        workload=_merge(read("workloads", name), o.get("workload")),
        traffic=_merge(read("traffic", entry["traffic"]), o.get("traffic")),
        end_to_end=[m for m in spec["end_to_end"] if m["name"] in reported],
        per_layer=[m for m in spec["per_layer"]
                   if name in m.get("workloads", [name] if m["moves"] in reported else [])],
        bench=bench,
    )


class Spans:
    """Host spans around the harness's calls into each layer; they
    reach the profiler's trace only while it records."""

    def __init__(self):
        self.on = False

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")


class Window:
    """The measured window: ``seconds`` of work, of which the first
    ``trace_seconds`` are traced in a ``--trace 1`` run.  Drivers call
    :meth:`done` after each unit of work and :meth:`close` once the
    last one is finished; counters they :meth:`count` while the trace
    records go to the per-layer metrics."""

    def __init__(self, seconds: float, trace: bool, trace_seconds: float, spans: Spans):
        self.seconds, self.trace, self.spans = seconds, trace, spans
        self.trace_seconds = min(seconds, trace_seconds)
        self.traced: dict[str, float] = {}
        self._span = None
        self.gc_pauses: list[float] = []

    def _on_gc(self, phase, _info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.gc_pauses.append(time.perf_counter() - self._gc_t)

    def open(self) -> float:
        import jax

        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            self.spans.on = True
            self._span = self.spans("window")
            self._span.__enter__()
        gc.callbacks.append(self._on_gc)
        self.t0 = time.perf_counter()
        return self.t0

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def count(self, key: str, n: float = 1) -> None:
        if self._span is not None:
            self.traced[key] = self.traced.get(key, 0) + n

    def done(self, sync=None) -> bool:
        """True once the window's time is up.  Ends the traced
        sub-window once it is over, after ``sync()`` has waited for the
        work in flight, so the trace holds whole units of work."""
        t = self.elapsed()
        if self._span is not None and t >= self.trace_seconds:
            if sync is not None:
                sync()
            self.traced["seconds"] = self.elapsed()
            self._stop_trace()
        return t >= self.seconds

    def _stop_trace(self):
        import jax

        self._span.__exit__(None, None, None)
        self._span = None
        self.spans.on = False
        jax.profiler.stop_trace()

    def close(self) -> float:
        self.t1 = time.perf_counter()
        gc.callbacks.remove(self._on_gc)
        if self._span is not None:
            self.traced["seconds"] = self.t1 - self.t0
            self._stop_trace()
        return self.t1 - self.t0


class CompileCounter:
    """Counts, while armed, programs compiled or loaded from the
    persistent cache: either means a trace and a compile step ran."""

    COMPILED = "/jax/core/compile/backend_compile_duration"
    LOADED = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, _secs, **_kw: self._on(name))
        jax.monitoring.register_event_listener(lambda name, **_kw: self._on(name))

    def _on(self, name):
        if self.armed and name in (self.COMPILED, self.LOADED):
            self.n += 1


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak_bytes(n: int) -> int | None:
    """Peak bytes in use on the fullest of the first ``n`` devices."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()[:n]]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float = T_START) -> dict:
    """Set up, measure and check one run; returns the result object."""
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    driver = cell.module("drivers", cell.workload["driver"])
    spans = Spans()
    counter = CompileCounter()
    session = driver.setup(cell, seed, spans, log)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s}")

    win = Window(seconds, trace, float(cell.workload.get("trace_seconds", seconds)), spans)
    counter.armed = True
    measured = driver.window(session, win)
    counter.armed = False
    log(f"gc in window: {len(win.gc_pauses)} collections, longest {max(win.gc_pauses, default=0) * 1e3:.2f} ms")
    peak = memory_peak_bytes(cell.chips)
    checks = driver.check(session, measured, log)
    del session
    gc.collect()

    metrics: dict[str, dict] = {}
    extra: dict = {}
    if not trace:
        values = dict(measured["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    device = device_info()
    device["count"] = cell.chips
    device["memory_peak_bytes"] = peak
    if trace:
        trace_mod = load_module(cell.bench / "trace.py")
        tr = trace_mod.load(TRACE_DIR)
        ctx = MetricContext(cell=cell, trace=tr, traced=win.traced, measured=measured,
                            trace_mod=trace_mod, bench=cell.bench)
        for m in cell.per_layer:
            v = cell.module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace_mod.busy_s(tr)
        device["window_s"] = tr.window_s
        extra["breakdown"] = {"device_ops": trace_mod.top_ops(tr), "idle_gaps": trace_mod.idle_gaps(tr)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # a compile in the window spoils the timing, not the outputs: it is
    # printed beside the compared numbers but does not decide `correct`
    correct = verdict(checks)
    log(f"compiles_in_window {counter.n} limit 0")
    for c in checks:
        log(f"check {c['name']} {c['value']} limit {c['limit']} {'ok' if c['ok'] else 'FAIL'}")
    return {
        "correct": bool(correct),
        "attempted": int(measured["attempted"]),
        "failed": int(measured["failed"]),
        "metrics": metrics,
        "device": device,
        **extra,
        "compiles_in_window": counter.n,
        "checks": {c["name"]: [c["value"], c["limit"]] for c in checks},
    }


def verdict(checks: list[dict]) -> bool:
    """``correct``: every compared number within its limit."""
    return all(c["ok"] for c in checks)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader is given."""

    cell: Cell
    trace: object
    traced: dict
    measured: dict
    trace_mod: object
    bench: Path

    def peaks(self) -> dict:
        import jax

        table = json.loads((self.bench / "peaks.json").read_text())["devices"]
        kind = jax.devices()[0].device_kind
        if kind not in table:
            raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
        return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    cell = load_cell(args.workload)
    import jax

    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    # every program of the cell, however quick to compile, is cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"bench/run.py needs a TPU; JAX found {dev['platform']}", file=sys.stderr)
        return 2
    if dev["count"] < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips; JAX sees {dev['count']}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    del jax
    return 0


if __name__ == "__main__":
    sys.exit(main())
