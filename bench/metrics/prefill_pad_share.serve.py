"""Share of the prefill positions run in the traced window that were
bucket padding (percent): 1 - summed ``prompt`` / summed ``bucket`` of
the window's ``repro.serve.prefill`` host spans."""

import importlib.util


def read(ctx):
    spec = importlib.util.spec_from_file_location("bench_program_trace", ctx.bench / "program_trace.py")
    pt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pt)
    spans = pt.load(ctx).spans_named("serve.prefill", ctx.trace.window)
    positions = sum(stats.get("bucket", 0) for _, stats in spans)
    if not positions:
        return None
    return 100.0 * (1.0 - sum(stats.get("prompt", 0) for _, stats in spans) / positions)
