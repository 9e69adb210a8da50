"""Share of the traced window the device spent admitting requests
(percent): every program but the decode slice, that is the bucketed
B=1 prefill (a jitted lambda, so found by elimination) and the scatter
of its cache into the slot."""


def read(ctx):
    n, secs = ctx.trace_mod.module_runs(ctx.trace, lambda name: "slice_fn" not in name)
    return 100.0 * secs / ctx.trace.window_s if n else None
