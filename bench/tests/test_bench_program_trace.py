"""The program's own spans and scopes in a trace (``bench/program_trace.py``)
and the per-layer readers built on them, on small traces written here as
XSpace text protos with ``stats`` and ``stat_metadata``."""

from __future__ import annotations

import pytest

from _bench_tiny import harness, module

jax_profiler = pytest.importorskip("jax.profiler")


def _stat(ids: dict, name: str, value) -> str:
    v = f'str_value: "{value}"' if isinstance(value, str) else f"int64_value: {value}"
    return f"stats {{ metadata_id: {ids[name]} {v} }}"


def xspace(planes: dict, meta_stats: dict | None = None) -> str:
    """``{plane: {line: [(name, start_ns, dur_ns[, {stat: value}]), ...]}}``
    -> text proto; ``meta_stats`` gives ``{plane: {event name: {stat:
    value}}}`` kept on the event's metadata, as the TPU trace keeps
    ``tf_op``."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        mstats = (meta_stats or {}).get(pname, {})
        names = sorted({e[0] for evs in lines.values() for e in evs} | set(mstats))
        ids = {n: i + 1 for i, n in enumerate(names)}
        snames = sorted({k for evs in lines.values() for e in evs if len(e) > 3 for k in e[3]}
                        | {k for st in mstats.values() for k in st})
        sids = {n: i + 1 for i, n in enumerate(snames)}
        body = [f'id: {pid} name: "{pname}"']
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            ev = " ".join(
                f"events {{ metadata_id: {ids[e[0]]} offset_ps: {e[1] * 1000} duration_ps: {e[2] * 1000} "
                + " ".join(_stat(sids, k, v) for k, v in (e[3] if len(e) > 3 else {}).items()) + " }"
                for e in evs)
            body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 {ev} }}')
        for n, i in ids.items():
            st = " ".join(_stat(sids, k, v) for k, v in mstats.get(n, {}).items())
            body.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" {st} }} }}')
        body += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for n, i in sids.items()]
        out.append("planes { " + " ".join(body) + " }")
    return "\n".join(out)


CP = "%cp.2 = f32[4]{0} collective-permute(f32[4]{0} %x)"
# window [100, 1100).  Device 0 keeps op_name on the ops' metadata:
# optimizer 100..300 and 1000..1100 (clipped), 300 ns; a loop scoped
# as the optimizer (left out: it spans its body); NAP's inter phase
# 400..520 (permute + select), 120 ns; its intra reduce; an op with no
# scope.  Device 1 keeps op_name on the event itself for the optimizer
# op (100 ns; its metadata says otherwise) and on the metadata for the
# permute (60 ns).
OPT = "jit(step)/shard_map/repro.train.optimizer/add"
INTER = "jit(chain)/while/body/repro.comm.nap/inter/"
STEP = {
    "/device:TPU:0": {
        "XLA Ops": [("fusion.1", 100, 200), ("while.5", 100, 300), (CP, 400, 100), ("select.6", 500, 20),
                    ("all-reduce.3", 520, 80), ("fusion.9", 600, 100), ("fusion.1", 1000, 200)],
    },
    "/device:TPU:1": {
        "XLA Ops": [("fusion.1", 200, 100, {"tf_op": "jit(step)/repro.train.optimizer/mul"}), (CP, 400, 60)],
    },
    "/host:CPU": {"python": [("bench.window", 100, 1000)]},
}
STEP_META = {
    "/device:TPU:0": {"fusion.1": {"tf_op": OPT}, "while.5": {"tf_op": "jit(step)/repro.train.optimizer/while"},
                      CP: {"tf_op": INTER + "ppermute"}, "select.6": {"tf_op": INTER + "select_n"},
                      "all-reduce.3": {"tf_op": "jit(chain)/while/body/repro.comm.nap/intra/psum"},
                      "fusion.9": {"hlo_category": "non-fusion elementwise"}},
    "/device:TPU:1": {"fusion.1": {"tf_op": "jit(step)/unscoped/mul"}, CP: {"tf_op": INTER + "ppermute"}},
}

# one serving device: busy 130..380 (two prefill runs) and 460..780 (the
# slice).  Host spans: admit 100..400 holding prefills 120..300 (bucket
# 64, prompt 16) and 300..390 (bucket 128, prompt 100); slice 400..450;
# wait 450..800; record 800..900 (2 steps); admit 950..1050; a prefill
# past the window (1150) that no reader may count.
SERVE = {
    "/device:TPU:0": {
        "XLA Ops": [("fusion.p", 130, 250), ("fusion.s", 460, 320)],
        "XLA Modules": [("jit_prefill(3)", 130, 125), ("jit_prefill(3)", 255, 125), ("jit_slice_fn(7)", 460, 320)],
    },
    "/host:CPU": {
        "python": [("bench.window", 100, 1000), ("bench.serve.admit_prefill_slice", 100, 800),
                   ("repro.serve.admit", 100, 300), ("repro.serve.prefill", 120, 180, {"bucket": 64, "prompt": 16}),
                   ("repro.serve.prefill", 300, 90, {"bucket": 128, "prompt": 100}),
                   ("repro.serve.slice", 400, 50), ("repro.serve.wait", 450, 350),
                   ("repro.serve.record", 800, 100, {"steps": 2, "tokens": 16}), ("repro.serve.admit", 950, 100),
                   ("repro.serve.prefill", 1150, 10, {"bucket": 512, "prompt": 500})],
    },
}


def _ctx(planes, meta=None, traced=None):
    run = harness()
    text = xspace(planes, meta)
    tr = module("trace.py").from_profile(jax_profiler.ProfileData.from_text_proto(text))
    tr.program = module("program_trace.py").from_xspace(
        jax_profiler.ProfileData.text_proto_to_serialized_xspace(text), module("trace.py"))
    return run.MetricContext(cell=None, trace=tr, traced=traced or {}, measured={},
                             trace_mod=module("trace.py"), bench=run.BENCH)


def read(metric, ctx):
    return module(f"metrics/{metric}.py").read(ctx)


@pytest.mark.parametrize("op_name, path", [
    ("jit(chain)/while/body/repro.comm.nap/inter/ppermute", "repro.comm.nap/inter/ppermute"),
    ("jit(step)/transpose(jvp(repro.train.fwd_bwd))/dot", ""),
    ("jit(step)/repro.train.grad_sync/repro.grad_sync.bucket0/repro.comm.mla/inter/all-to-all",
     "repro.train.grad_sync/repro.grad_sync.bucket0/repro.comm.mla/inter/all-to-all"),
    ("jit(f)/add", ""),
])
def test_scope_path(op_name, path):
    assert module("program_trace.py").scope_path(op_name) == path


@pytest.mark.parametrize("path, name, phase, want", [
    ("repro.comm.nap/inter/ppermute", "comm.*", "inter", True),
    ("repro.comm.nap/intra/psum", "comm.*", "inter", False),
    ("repro.comm.nap/intra/inter_x", "comm.*", "inter", False),
    ("repro.train.grad_sync/repro.grad_sync.bucket1/repro.comm.mla/inter/a", "comm.*", "inter", True),
    ("repro.train.optimizer/add", "train.optimizer", None, True),
    ("repro.train.optimizer_x/add", "train.optimizer", None, False),
])
def test_in_scope(path, name, phase, want):
    assert module("program_trace.py").in_scope(path, name, phase) is want


def test_spans_carry_their_counts_and_scoped_ops_their_paths():
    prog = _ctx(SERVE).trace.program
    assert [e.name for e, _ in prog.spans][:3] == ["repro.serve.admit", "repro.serve.prefill", "repro.serve.prefill"]
    pre = prog.spans_named("serve.prefill", (100, 1100))
    assert [st for _, st in pre] == [{"bucket": 64, "prompt": 16}, {"bucket": 128, "prompt": 100}]
    assert prog.spans_named("serve.record", (100, 1100))[0][1] == {"steps": 2, "tokens": 16}
    assert prog.scoped == {}  # the serving trace scopes no device op
    step = _ctx(STEP, STEP_META).trace.program
    assert step.spans == []
    paths = {e.name: p for e, p in step.scoped["/device:TPU:0"]}
    assert paths == {"fusion.1": "repro.train.optimizer/add", "while.5": "repro.train.optimizer/while",
                     "cp.2": "repro.comm.nap/inter/ppermute", "select.6": "repro.comm.nap/inter/select_n",
                     "all-reduce.3": "repro.comm.nap/intra/psum"}
    # the event's own stat wins over its metadata's
    assert [p for e, p in step.scoped["/device:TPU:1"] if e.name == "fusion.1"] == ["repro.train.optimizer/mul"]


def test_scoped_op_time_per_call():
    # inter: device 0 120 ns, device 1 60 ns -> 90 ns a device, 3 calls
    ctx = _ctx(STEP, STEP_META, {"calls": 3})
    assert read("inter_node_us.allreduce", ctx) == pytest.approx(90e-9 / 3 * 1e6)
    assert read("inter_node_us.allreduce", _ctx(STEP, STEP_META)) is None


def test_scoped_op_time_per_step():
    # optimizer: device 0 300 ns (the loop left out), device 1 100 ns
    # (its event's stat) -> 200 ns a device, 4 steps
    ctx = _ctx(STEP, STEP_META, {"steps": 4})
    assert read("optimizer_ms.train", ctx) == pytest.approx(200e-9 / 4 * 1e3)


def test_prefill_positions_and_padding_from_span_stats():
    ctx = _ctx(SERVE)
    # two jit_prefill runs of 125 ns over 64 + 128 positions; the span
    # past the window is not counted
    assert read("prefill_pos_ms.serve", ctx) == pytest.approx(250e-9 / 192 * 1e3)
    assert read("prefill_pad_share.serve", ctx) == pytest.approx(100 * (1 - 116 / 192))


def test_innermost_span_at_each_instant():
    pt, ev = module("program_trace.py"), module("trace.py").Event
    spans = [ev("a", 0, 100), ev("b", 10, 40), ev("c", 40, 50), ev("d", 200, 210)]
    assert pt.innermost(spans) == [(0, 10, "a"), (10, 40, "b"), (40, 50, "c"), (50, 100, "a"), (200, 210, "d")]


def test_idle_time_split_across_nested_spans_without_wait():
    ctx = _ctx(SERVE)
    pt = module("program_trace.py")
    spans = [e for e, _ in ctx.trace.program.spans]
    # idle gaps 100..130, 380..460, 780..1100, each instant to the
    # innermost span; 900..950 and 1050..1100 lie under no span
    assert pt.idle_under(ctx, spans, "/device:TPU:0") == {
        "repro.serve.admit": 20 + 10 + 100, "repro.serve.prefill": 10 + 10, "repro.serve.slice": 50,
        "repro.serve.wait": 10 + 20, "repro.serve.record": 100}
    # 300 ns held by host work (wait left out) over 2 decode steps
    assert read("host_idle_ms.serve", ctx) == pytest.approx(300e-9 / 2 * 1e3)


@pytest.mark.parametrize("metric", ["inter_node_us.allreduce", "optimizer_ms.train", "prefill_pos_ms.serve",
                                    "prefill_pad_share.serve", "host_idle_ms.serve"])
def test_readers_find_nothing_without_program_names(metric):
    # a trace of a program that writes no repro.* span or scope (an
    # older commit): the harness's spans and unscoped ops only
    planes = {
        "/device:TPU:0": {"XLA Ops": [("fusion.1", 100, 200), (CP, 400, 100)],
                          "XLA Modules": [("jit__lambda(3)", 100, 200), ("jit_slice_fn(7)", 400, 100)]},
        "/host:CPU": {"python": [("bench.window", 100, 1000), ("bench.serve.slice", 100, 500)]},
    }
    ctx = _ctx(planes, {"/device:TPU:0": {"fusion.1": {"tf_op": "jit(step)/add"}}},
               {"calls": 3, "steps": 4, "decode_steps": 2})
    assert read(metric, ctx) is None


def test_the_run_is_parsed_once_from_the_harness_trace_dir(tmp_path):
    pt, tm = module("program_trace.py"), module("trace.py")
    text = xspace(SERVE)
    run_dir = tmp_path / pt.TRACE_DIR / "plugins" / "profile" / "1"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(jax_profiler.ProfileData.text_proto_to_serialized_xspace(text))
    tr = tm.from_profile(jax_profiler.ProfileData.from_text_proto(text))
    ctx = harness().MetricContext(cell=None, trace=tr, traced={}, measured={}, trace_mod=tm, bench=tmp_path / "bench")
    prog = pt.load(ctx)
    assert len(prog.spans) == 8 and tr.program is prog
    assert pt.load(ctx) is prog
