"""Device time of one prefill position (milliseconds): the serving
engine's prefill program (``jit_prefill``) runs that start in the traced
window, over the positions they ran, which is the summed ``bucket`` of
the window's ``repro.serve.prefill`` host spans (padding included)."""

import importlib.util


def read(ctx):
    spec = importlib.util.spec_from_file_location("bench_program_trace", ctx.bench / "program_trace.py")
    pt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pt)
    spans = pt.load(ctx).spans_named("serve.prefill", ctx.trace.window)
    positions = sum(stats.get("bucket", 0) for _, stats in spans)
    if not positions:
        return None
    n, secs = ctx.trace_mod.module_runs(ctx.trace, lambda name: name.startswith("jit_prefill"))
    return secs / positions * 1e3 if n else None
