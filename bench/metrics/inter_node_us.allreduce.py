"""Device time of the ops in the inter-node phase of a collective engine
(scope ``repro.comm.<engine>/inter``: NAP's collective-permute rounds
with their masked folds) in the traced window, per allreduce call the
harness made there (microseconds, mean over chips)."""

import importlib.util


def read(ctx):
    calls = ctx.traced.get("calls", 0)
    if not calls:
        return None
    spec = importlib.util.spec_from_file_location("bench_program_trace", ctx.bench / "program_trace.py")
    pt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pt)
    secs = pt.scoped_seconds(ctx, lambda path: pt.in_scope(path, "comm.*", phase="inter"))
    return secs / calls * 1e6 if secs else None
