"""Share of the traced window in which no op ran on the device (percent,
mean over the cell's chips): 1 - union of op intervals / window."""


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * ctx.trace_mod.idle_share(ctx.trace)
