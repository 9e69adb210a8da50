"""Device time per decode step of the serving engine's slice program
(``jit_slice_fn``) in the traced window (milliseconds): the slice's
executions on the device over the decode steps they ran."""


def read(ctx):
    steps = ctx.traced.get("decode_steps", 0)
    if not steps:
        return None
    n, secs = ctx.trace_mod.module_runs(ctx.trace, lambda name: "slice_fn" in name)
    return secs / steps * 1e3 if n else None
