"""Device time of the optimizer's ops (scope ``repro.train.optimizer``:
the learning rate and the AdamW update of every weight) in the traced
window, per training step the harness ran there (milliseconds, mean over
chips)."""

import importlib.util


def read(ctx):
    steps = ctx.traced.get("steps", 0)
    if not steps:
        return None
    spec = importlib.util.spec_from_file_location("bench_program_trace", ctx.bench / "program_trace.py")
    pt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pt)
    secs = pt.scoped_seconds(ctx, lambda path: pt.in_scope(path, "train.optimizer"))
    return secs / steps * 1e3 if secs else None
