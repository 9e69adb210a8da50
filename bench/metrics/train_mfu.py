"""Model FLOP utilisation of the traced window: the step's required
FLOPs per token (``bench/flops.py``) x tokens trained per second, over
the cell's chips x the device kind's bf16 peak (``bench/peaks.json``),
in percent.  The rate is the traced window's own: tokens over its
host-clock length."""

import importlib.util


def read(ctx):
    tokens, secs = ctx.traced.get("tokens", 0), ctx.traced.get("seconds", 0)
    if not tokens or not secs:
        return None
    spec = importlib.util.spec_from_file_location("bench_flops", ctx.bench / "flops.py")
    flops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops)
    m = ctx.measured
    per_token = flops.train_flops_per_token(ctx.cell.config, m["layers"], m["seq_len"])
    peak = ctx.peaks()["bf16_flops_per_s"] * ctx.cell.chips
    return 100.0 * per_token * tokens / secs / peak
