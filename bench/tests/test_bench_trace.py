"""The trace reduction and the per-layer readers, on a small trace
written here as an XSpace text proto (the profiler's own format)."""

from __future__ import annotations

import pytest

from _bench_tiny import harness, module

jax_profiler = pytest.importorskip("jax.profiler")


def xspace(planes: dict) -> str:
    """``{plane: {line: [(name, start_ns, dur_ns), ...]}}`` -> text proto."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = sorted({e[0] for evs in lines.values() for e in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = [f'id: {pid} name: "{pname}"']
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            ev = " ".join(f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} duration_ps: {d * 1000} }}"
                          for n, s, d in evs)
            body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 {ev} }}')
        body += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for n, i in ids.items()]
        out.append("planes { " + " ".join(body) + " }")
    return "\n".join(out)


# window [100, 1100) ns; device 0 busy 100..400 (fusion + overlapping
# all-reduce), 600..700 (collective-permute), 1000..1200 (clipped to
# 1100); device 1 busy 100..600.
PLANES = {
    "/device:TPU:0": {
        "XLA Ops": [("fusion.1", 50, 250),
                    ("%psum_invariant.2 = f32[1,16]{1,0:T(1,128)S(1)} all-reduce(f32[1,16]{1,0} %p)", 200, 200),
                    ("collective-permute.3", 600, 100), ("fusion.1", 1000, 200)],
        "XLA Modules": [("jit__lambda(12)", 100, 300), ("jit_slice_fn(7)", 600, 100), ("jit_slice_fn(7)", 1000, 200),
                        ("jit_slice_fn(7)", 1150, 10)],
    },
    "/device:TPU:1": {
        "XLA Ops": [("fusion.1", 100, 500), ("while.4", 100, 500)],
        "XLA Modules": [("jit__lambda(12)", 100, 300), ("jit_slice_fn(7)", 600, 100), ("jit_slice_fn(7)", 1000, 200)],
    },
    "/host:CPU": {
        "python": [("bench.window", 100, 1000), ("bench.serve.slice", 380, 300),
                   ("bench.serve.admit_prefill_slice", 700, 400), ("other", 0, 50)],
    },
}


@pytest.fixture(scope="module")
def tr():
    pd = jax_profiler.ProfileData.from_text_proto(xspace(PLANES))
    return module("trace.py").from_profile(pd)


def test_window_is_the_harness_span(tr):
    assert tr.window == (100, 1100)
    assert tr.window_s == pytest.approx(1e-6)
    assert sorted(tr.ops) == ["/device:TPU:0", "/device:TPU:1"]


@pytest.mark.parametrize("intervals, want", [
    ([(0, 10), (5, 20), (30, 40)], [(0, 20), (30, 40)]),
    ([(30, 40), (0, 10)], [(0, 10), (30, 40)]),
    ([(-5, 3), (95, 200)], [(0, 3), (95, 100)]),
    ([(10, 10), (200, 300)], []),
    ([(0, 50), (10, 20), (50, 60)], [(0, 60)]),
])
def test_union_merges_and_clips(intervals, want):
    assert module("trace.py").union(intervals, 0, 100) == want


def test_busy_and_idle_share(tr):
    t = module("trace.py")
    # device 0: 100..400 + 600..700 + 1000..1100 = 500 ns; device 1: 500 ns
    assert t.busy_ns(tr, "/device:TPU:0") == 500
    assert t.busy_ns(tr, "/device:TPU:1") == 500
    assert t.busy_s(tr) == pytest.approx(500e-9)
    assert t.idle_share(tr) == pytest.approx(0.5)


def test_op_totals_and_collectives(tr):
    t = module("trace.py")
    # collectives on device 0: 200 + 100 ns, none on device 1: mean 150 ns
    assert t.op_seconds(tr, t.is_collective) == pytest.approx(150e-9)
    # fusion.1 clipped: dev0 (300-100) + (1100-1000) = 300, dev1 500 -> mean 400
    top = dict(t.top_ops(tr))
    assert top["fusion.1"] == pytest.approx(400e-9)
    assert t.top_ops(tr)[0][0] == "fusion.1"
    assert "while.4" not in top  # a loop's event spans its body's ops
    assert t.is_collective("all-gather-start") and not t.is_collective("fusion")
    text = "%psum_invariant.2 = f32[1,16]{1,0:T(1,128)S(1)} all-reduce(f32[1,16]{1,0} %p)"
    assert t.short_name(text) == "psum_invariant.2" and t.opcode(text) == "all-reduce"
    assert t.opcode("%cp = (f32[2]{0:T(128)S(1)}, u32[]{:S(2)}) collective-permute-start(f32[2]{0} %x)") \
        == "collective-permute-start"
    assert t.opcode("collective-permute.3") == "collective-permute"
    assert any(label.startswith("%psum_invariant.2 = ") for label, _ in t.top_ops(tr))


def test_module_runs_count_starts_in_window(tr):
    t = module("trace.py")
    n, secs = t.module_runs(tr, lambda name: "slice" in name)
    # two starts per device in [100, 1100) (1150 is past the window);
    # a run counts whole once it starts inside
    assert n == 2
    assert secs == pytest.approx(300e-9)


def test_idle_gaps_are_named_by_host_span(tr):
    gaps = module("trace.py").idle_gaps(tr)
    assert gaps[0][0] == "bench.serve.admit_prefill_slice"
    assert gaps[0][1] == pytest.approx(300e-9)
    assert gaps[1] == ["bench.serve.slice", pytest.approx(200e-9)]


def _ctx(tr, traced, measured=None, chips=2):
    run = harness()
    cell = run.load_cell("allreduce-2x2.small")
    cell.chips = chips

    class Ctx(run.MetricContext):
        def peaks(self):
            return {"bf16_flops_per_s": 1e12}

    return Ctx(cell=cell, trace=tr, traced=traced, measured=measured or {},
               trace_mod=module("trace.py"), bench=run.BENCH)


def test_per_call_division(tr):
    read = module("metrics/allreduce_device_us.py").read
    assert read(_ctx(tr, {"calls": 3})) == pytest.approx(150e-9 / 3 * 1e6)
    assert read(_ctx(tr, {})) is None


def test_per_step_and_share_readers(tr):
    assert module("metrics/decode_step_ms.serve.py").read(_ctx(tr, {"decode_steps": 5})) == pytest.approx(
        300e-9 / 5 * 1e3)
    assert module("metrics/prefill_share.serve.py").read(_ctx(tr, {})) == pytest.approx(30.0)
    for name in ("allreduce", "train", "serve"):
        assert module(f"metrics/idle_share.{name}.py").read(_ctx(tr, {})) == pytest.approx(50.0)


def test_mfu_reader(tr):
    cfg = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
           "num_key_value_heads": 4, "vocab_size": 512}
    ctx = _ctx(tr, {"tokens": 1000, "seconds": 2.0}, {"layers": 2, "seq_len": 32})
    ctx.cell.config = cfg
    per_token = module("flops.py").train_flops_per_token(cfg, 2, 32)
    assert module("metrics/train_mfu.py").read(ctx) == pytest.approx(100 * per_token * 500 / 2e12)
    assert module("metrics/train_mfu.py").read(_ctx(tr, {})) is None


def test_readers_find_nothing_without_device_ops():
    pd = jax_profiler.ProfileData.from_text_proto(xspace({"/host:CPU": {"python": [("bench.window", 0, 10)]}}))
    tr = module("trace.py").from_profile(pd)
    assert module("metrics/idle_share.serve.py").read(_ctx(tr, {})) is None
    assert module("metrics/allreduce_device_us.py").read(_ctx(tr, {"calls": 4})) is None
    assert module("trace.py").idle_gaps(tr) == []


def test_trace_without_window_span_is_refused():
    pd = jax_profiler.ProfileData.from_text_proto(xspace({"/host:CPU": {"python": [("bench.x", 0, 10)]}}))
    with pytest.raises(ValueError):
        module("trace.py").from_profile(pd)
