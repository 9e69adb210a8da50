"""Data-parallel training through ``make_dp_train_step``.

Set-up builds one object, the compiled step with its state (weights
from the seed on the device, Adam moments), and drives it through the
first three steps with the window's own call and feed; the window then
continues with the same object on fresh batches, one step in flight
while the host makes the next batch.  The readings of those first steps
(each loss, the first gradient as Adam's first moment holds it, the
weights' change over the three) are compared after the window with the
plain float32 reference following the same three steps.
"""

from __future__ import annotations

import numpy as np

FIRST_STEPS = 3


def setup(cell, seed, spans, log):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import OptimizerConfig
    from repro.core import comm
    from repro.launch.mesh import make_mesh, mesh_topology
    from repro.launch.steps import make_dp_train_step
    from repro.optim import adamw_init

    s = setup_reference_only(cell, seed)
    lm = cell.module("drivers", "lm_common")
    to_program, from_program = lm.to_program, lm.from_program
    cfg, wl, o = cell.config, cell.workload, s["opt"]
    opt_cfg = OptimizerConfig(
        lr=o["lr"], schedule=o["schedule"], warmup_steps=o["warmup_steps"],
        betas=tuple(o["betas"]), eps=o["eps"], weight_decay=o["weight_decay"],
        grad_clip=o["grad_clip"],
    )
    mesh = make_mesh(wl["mesh"]["shape"], wl["mesh"]["axes"])
    topo = mesh_topology(mesh)
    rep = NamedSharding(mesh, P())
    batch_sharding = NamedSharding(mesh, P(topo.axes, None))
    build, key = s["ref"].make_weights(s["seed32"], cfg, s["layers"])

    def init(key):
        params = to_program(build(key))
        return {"params": params, "opt": adamw_init(params, moment_dtype=opt_cfg.moment_dtype)}

    state = jax.jit(init, out_shardings=rep)(key)
    step = jax.jit(make_dp_train_step(lm.program_config(cfg, s["layers"]), opt_cfg, mesh, comm.CommPolicy()),
                   donate_argnums=(0,))

    def batch(i):
        b = s["gen"].lm_batch(s["mix"], seed, i, s["rows"], cfg["vocab_size"])
        return {k: jax.device_put(v, batch_sharding) for k, v in b.items()}

    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                               for x in jax.tree.leaves(from_program(t))])
    losses, readings = [], {}
    for i in range(FIRST_STEPS):
        state, m = step(state, batch(i))
        losses.append(m["loss"])
        if i == 0:
            readings["grad"] = [float(x) / (1 - opt_cfg.betas[0]) for x in norms(state["opt"].mu)]
    p0 = jax.jit(lambda k: to_program(build(k)), out_shardings=rep)(key)
    dnorm = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
                                  for x, y in zip(jax.tree.leaves(from_program(a)), jax.tree.leaves(from_program(b)))])
    readings["change"] = [float(x) for x in dnorm(state["params"], p0)]
    readings["losses"] = [float(x) for x in losses]
    del p0
    log(f"first losses {readings['losses']}")
    log(f"mesh {tuple(mesh.shape.values())} group {topo.group} layers {s['layers']} rows/step {s['rows']}")
    return dict(s, state=state, step=step, batch=batch, readings=readings,
                seq_len=int(s["mix"]["seq_len"]))


def window(s, win):
    step, batch = s["step"], s["batch"]
    tokens_per_step = s["rows"] * s["seq_len"]
    state, i, n = s["state"], FIRST_STEPS, 0
    inflight, ended = [], []

    def wait_one():
        with win.spans("train.wait"):
            inflight.pop(0).block_until_ready()
        ended.append(win.elapsed())

    def sync():
        while inflight:
            wait_one()

    win.open()
    while True:
        with win.spans("train.batch"):
            b = batch(i)
        with win.spans("train.step"):
            state, m = step(state, b)
        inflight.append(m["loss"])
        i += 1
        n += 1
        win.count("steps")
        win.count("tokens", tokens_per_step)
        if len(inflight) > 1:
            wait_one()
        if win.done(sync):
            break
    sync()
    secs = win.close()
    s["state"] = state
    return {
        "attempted": n, "failed": 0,
        "metrics": {"train_tokens_per_s": n * tokens_per_step / secs},
        "window_s": secs, "steps": n, "layers": s["layers"], "seq_len": s["seq_len"],
        "step_ends": ended,
    }


def log_stalls(ended: list[float], log):
    """The steadiness of the steps: their median interval, and the
    longest with the step and the time at which it ended."""
    if len(ended) > 1:
        gaps_s = np.diff(ended)
        k = int(np.argmax(gaps_s))
        log(f"step intervals: median {np.median(gaps_s) * 1e3:.2f} ms, longest {gaps_s[k] * 1e3:.2f} ms "
            f"(step {k + 1} of the window, ended at {ended[k + 1]:.3f} s)")


def gaps(prog: dict, ref: dict, names: list[str], log) -> dict:
    """The compared numbers.  Norm gaps are taken by the worst leaf:
    |program's norm - reference's| over the larger of the reference's
    norm of that leaf and of the median leaf.  Leaves whose reference
    gradient is under a thousandth of the median leaf's (nought to
    rounding) are left out of the change."""
    def worst(a, b, keep):
        med = float(np.median(b))
        rows = [(abs(x - y) / max(y, med), n) for x, y, n, k in zip(a, b, names, keep) if k]
        return max(rows)

    g_med = float(np.median(ref["grad_raw"]))
    keep = [g >= 1e-3 * g_med for g in ref["grad_raw"]]
    loss = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    grad, g_leaf = worst(prog["grad"], ref["grad"], [True] * len(names))
    change, c_leaf = worst(prog["change"], ref["change"], keep)
    log(f"losses program {prog['losses']} reference {ref['losses']}")
    log(f"worst grad leaf {g_leaf}, worst change leaf {c_leaf}; left out {[n for n, k in zip(names, keep) if not k]}")
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change}


def reference_readings(s, precision="f32"):
    """The plain reference over the same three steps, on the first chip."""
    import jax

    gen, ref, cfg = s["gen"], s["ref"], s["cfg"]
    build, key = ref.make_weights(s["seed32"], cfg, s["layers"])
    dev = jax.devices()[0]
    make_w0 = lambda: jax.jit(build, out_shardings=jax.sharding.SingleDeviceSharding(dev))(key)  # noqa: E731
    batches = []
    for i in range(FIRST_STEPS):
        b = gen.lm_batch(s["mix"], s["seed"], i, s["rows"], cfg["vocab_size"])
        batches.append({k: jax.device_put(v.reshape((s["group"], -1) + v.shape[1:]), dev) for k, v in b.items()})
    return ref.train_readings(make_w0, batches, cfg, s["opt"], precision)


def judge(s, readings, log) -> list[dict]:
    """The comparison that decides ``correct``: ``readings`` of the
    first three steps against the float32 reference's, each gap beside
    its limit."""
    g = gaps(readings, reference_readings(s), s["names"], log)
    limits = s["limits"]
    return [{"name": k, "value": v, "limit": limits[k], "ok": v <= limits[k]} for k, v in g.items()]


def check(s, measured, log):
    import gc

    log_stalls(measured["step_ends"], log)
    s.pop("state")
    s.pop("step")
    gc.collect()
    return judge(s, s["readings"], log)


def control(cell, seed, log):
    """The reference in the program's place, computed with every matmul
    operand rounded to float8_e4m3, judged as a run is."""
    s = setup_reference_only(cell, seed)
    return judge(s, reference_readings(s, "fp8"), log)


def setup_reference_only(cell, seed):
    """What :func:`reference_readings` needs, without the program."""
    import jax

    gen = cell.module("traffic", "gen")
    ref = cell.module("reference", cell.config["reference"])
    layers = int(cell.workload["layers"])
    group = int(np.prod(cell.workload["mesh"]["shape"]))
    build, key = ref.make_weights(gen.jax_seed(seed), cell.config, layers)
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(jax.eval_shape(build, key))[0]]
    return {"gen": gen, "ref": ref, "cfg": cell.config, "seed": seed, "seed32": gen.jax_seed(seed),
            "layers": layers, "rows": int(cell.traffic["rows_per_chip"]) * group, "group": group,
            "mix": cell.traffic, "opt": cell.config["assumed"]["optimizer"], "names": names,
            "limits": cell.workload["limits"]}


def _fault_stale():
    from repro.launch import steps

    def wrap(orig):
        def make(*a, **k):
            f = orig(*a, **k)
            return lambda state, batch: (state, f(state, batch)[1])
        return make

    return steps, "make_dp_train_step", wrap


def _fault_half():
    from repro.launch import steps

    def wrap(orig):
        def make(*a, **k):
            f = orig(*a, **k)

            def step(state, batch):
                import jax.numpy as jnp

                mask = batch["loss_mask"]
                keep = (jnp.arange(mask.shape[0]) % 2 == 0)[:, None]
                return f(state, dict(batch, loss_mask=mask * keep))
            return step
        return make

    return steps, "make_dp_train_step", wrap


def _fault_no_exchange():
    from repro.core import comm

    def wrap(orig):
        def sync_grads(self, grads, *a, **k):
            return grads
        return sync_grads

    return comm.CommContext, "sync_grads", wrap


# planted faults: (owner, attribute, wrapper of the original)
FAULTS = {"stale": _fault_stale, "half": _fault_half, "no_exchange": _fault_no_exchange}
