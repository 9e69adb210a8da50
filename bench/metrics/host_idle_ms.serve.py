"""Device idle time that the serving engine's host work holds, per
decode step (milliseconds, mean over chips): idle time in the traced
window whose innermost ``repro.serve.*`` host span is one other than
``serve.wait`` (admission, prefill and slice dispatch, token recording),
over the decode steps of the window's ``serve.record`` spans."""

import importlib.util


def read(ctx):
    spec = importlib.util.spec_from_file_location("bench_program_trace", ctx.bench / "program_trace.py")
    pt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pt)
    prog = pt.load(ctx)
    steps = sum(stats.get("steps", 0) for _, stats in prog.spans_named("serve.record", ctx.trace.window))
    if not steps or not ctx.trace.ops:
        return None
    spans = [e for e, _ in prog.spans if e.name.startswith("repro.serve.")]
    held = 0
    for device in ctx.trace.ops:
        idle = pt.idle_under(ctx, spans, device)
        held += sum(ns for name, ns in idle.items() if name != "repro.serve.wait")
    return held / len(ctx.trace.ops) / steps * 1e-6
