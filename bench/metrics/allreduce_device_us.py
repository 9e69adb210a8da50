"""Device time of the collective ops in the traced window, per
allreduce call the harness made there (microseconds, mean over chips)."""


def read(ctx):
    calls = ctx.traced.get("calls", 0)
    if not calls:
        return None
    secs = ctx.trace_mod.op_seconds(ctx.trace, ctx.trace_mod.is_collective)
    return secs / calls * 1e6 if secs > 0 else None
