"""Allreduce latency, as OSU's ``osu_allreduce`` measures it.

Each launch runs ``calls_per_launch`` dependent allreduces of one payload
size in a ``fori_loop``; the window runs launches in the mix's seeded
order of sizes, one in flight while the next is dispatched, and ends in
``block_until_ready``.  A call is
``CommContext.allreduce`` under the default policy (auto dispatch)
inside a ``shard_map`` over the cell's mesh.  The calls are chained,
``y <- allreduce(y) / 4 + d`` with a per-chip offset ``d``, so no call
can start before the last one ended and every call sums distinct
values.  ``correct`` compares each size's chain on every chip with the
same chain in float64 (``bench/reference/allreduce_chain.py``).
"""

from __future__ import annotations

import numpy as np

CHECK = "allreduce_rel_err"


def _program(ctx, mesh, spec, calls: int):
    import jax
    from jax import lax

    def chain(x, d):
        return lax.fori_loop(0, calls, lambda i, y: ctx.allreduce(y) * 0.25 + d, x)

    return jax.jit(jax.shard_map(chain, mesh=mesh, in_specs=(spec, spec), out_specs=spec))


def setup(cell, seed, spans, log):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import comm
    from repro.launch.mesh import make_mesh, mesh_topology

    gen = cell.module("traffic", "gen")
    mix, wl = cell.traffic, cell.workload
    mesh = make_mesh(wl["mesh"]["shape"], wl["mesh"]["axes"])
    topo = mesh_topology(mesh)
    ctx = comm.CommContext(topo)
    spec = P(topo.axes)
    sharding = NamedSharding(mesh, spec)
    calls = int(mix["calls_per_launch"])
    fn = _program(ctx, mesh, spec, calls)
    sizes = gen.payload_sizes(mix)
    inputs, programs = {}, {}
    for nbytes in sizes:
        x, d = gen.payload_inputs(mix, seed, nbytes, topo.group)
        inputs[nbytes] = (x, d)
        args = (jax.device_put(x, sharding), jax.device_put(d, sharding))
        programs[nbytes] = args
        fn(*args).block_until_ready()  # compile (or load) and warm up
        log(f"size {nbytes} B -> {ctx.dispatch(nbytes).engine}")
    log(f"topology n_nodes={topo.n_nodes} ppn={topo.ppn} crossover={topo.crossover_bytes()}")
    return {
        "fn": fn, "args": programs, "inputs": inputs, "calls": calls,
        "ref": cell.module("reference", cell.config["reference"]),
        "limit": wl["limits"][CHECK],
        "schedule": gen.payload_schedule(mix, seed),
    }


def window(s, win):
    """Launches back to back: the next one is dispatched before the
    last one is waited for, so the device runs them without host gaps."""
    fn, calls = s["fn"], s["calls"]
    last, inflight = {}, []
    n = 0

    def wait_one():
        nbytes, out = inflight.pop(0)
        with win.spans("allreduce.wait"):
            out.block_until_ready()
        last[nbytes] = out

    def sync():
        while inflight:
            wait_one()

    win.open()
    for nbytes in s["schedule"]:
        with win.spans("allreduce.launch"):
            inflight.append((nbytes, fn(*s["args"][nbytes])))
        n += calls
        win.count("calls", calls)
        if len(inflight) > 1:
            wait_one()
        if win.done(sync):
            break
    sync()
    secs = win.close()
    s["last"] = last
    return {
        "attempted": n, "failed": 0,
        "metrics": {"allreduce_us": secs / n * 1e6},
        "window_s": secs, "calls": n,
    }


def judge(s, outs: dict, log) -> list[dict]:
    """The comparison that decides ``correct``: each size's chain in
    ``outs`` on every chip against the same chain in float64, the worst
    beside its limit."""
    ref, worst = s["ref"], 0.0
    for nbytes, out in sorted(outs.items()):
        x, d = s["inputs"][nbytes]
        err = ref.rel_err(np.asarray(out), ref.chain(x, d, s["calls"]))
        log(f"size {nbytes} B rel_err {err}")
        worst = max(worst, err)
    return [{"name": CHECK, "value": worst, "limit": s["limit"], "ok": worst <= s["limit"]}]


def check(s, measured, log):
    rows = judge(s, s["last"], log)
    s.clear()
    return rows


def control(cell, seed, log):
    """The reference in the program's place, in bfloat16 (the chain with
    every operation rounded to bfloat16), judged as a run is."""
    import ml_dtypes

    gen, mix = cell.module("traffic", "gen"), cell.traffic
    ref = cell.module("reference", cell.config["reference"])
    group = int(cell.config["n_nodes"]) * int(cell.config["ppn"])
    calls = int(mix["calls_per_launch"])
    inputs = {n: gen.payload_inputs(mix, seed, n, group) for n in gen.payload_sizes(mix)}
    outs = {n: ref.chain(x, d, calls, ml_dtypes.bfloat16) for n, (x, d) in inputs.items()}
    s = {"ref": ref, "inputs": inputs, "calls": calls, "limit": cell.workload["limits"][CHECK]}
    return judge(s, outs, log)


def _fault_no_exchange():
    from repro.core import comm

    return comm.CommContext, "allreduce", lambda orig: (lambda self, x, *a, **k: x)


def _fault_altered():
    from repro.core import comm

    def wrap(orig):
        def allreduce(self, x, *a, **k):
            y = orig(self, x, *a, **k)
            return y.at[..., 0].add(1.0)
        return allreduce

    return comm.CommContext, "allreduce", wrap


# planted faults: (owner, attribute, wrapper of the original)
FAULTS = {"no_exchange": _fault_no_exchange, "altered": _fault_altered}
