"""What the program itself wrote into a profiler trace: its ``repro.*``
host spans with their counts, and the ``repro.*`` scope path of each
device op (``repro.runtime.tracing`` in the program).

It reads the same ``.xplane.pb`` as ``bench/trace.py``, once a run, and
keeps the result on the harness's ``Trace`` object, so every reader of
the run shares one parse.  Spans and ops are ``trace.py``'s ``Event``s on
the same clock.  A trace of a program without such names (an older
commit) gives no spans and no scoped ops, and the readers then return
``None``.

An op's scope path comes from its ``op_name``, which the TPU trace keeps
in the stat ``tf_op`` of the op's event metadata (``ProfileData`` does not
expose metadata stats, so the file is parsed here against a subset of the
XSpace schema).
"""

from __future__ import annotations

import fnmatch
import glob
from pathlib import Path
from typing import NamedTuple

ROOT = "repro."
# the stat of an XLA op event (on the TPU, of its metadata) that holds
# op_name, with a trailing ":" there
OP_NAME_STATS = ("tf_op",)
# the harness's trace directory, beside the checkout's bench/
TRACE_DIR = ".bench_traces"


class Program(NamedTuple):
    spans: list  # (trace.Event, {stat: value}) of each repro.* host span
    scoped: dict  # device plane -> [(trace.Event, scope path)] of scoped ops

    def spans_named(self, name: str, window) -> list:
        """The spans named ``repro.<name>`` that start in ``window``."""
        lo, hi = window
        return [(e, st) for e, st in self.spans if e.name == ROOT + name and lo <= e.start < hi]


def scope_path(op_name: str) -> str:
    """``"jit(f)/while/body/repro.comm.nap/inter/ppermute"`` ->
    ``"repro.comm.nap/inter/ppermute"``: the op_name from its first
    ``repro.`` scope on, or ``""`` if it has none."""
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if p.startswith(ROOT):
            return "/".join(parts[i:])
    return ""


def in_scope(path: str, name: str, phase: str | None = None) -> bool:
    """Whether scope path ``path`` lies under a scope ``repro.<name>``
    (``name`` may hold ``*``), and right under it in ``phase`` if one is
    given: ``in_scope("repro.comm.nap/inter/add", "comm.*", "inter")``."""
    parts = path.split("/")
    for i, part in enumerate(parts):
        if fnmatch.fnmatchcase(part, ROOT + name) and (phase is None or parts[i + 1:i + 2] == [phase]):
            return True
    return False


# --- the XSpace subset -------------------------------------------------------


def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"str": F.TYPE_STRING, "i64": F.TYPE_INT64, "u64": F.TYPE_UINT64,
              "f64": F.TYPE_DOUBLE, "bytes": F.TYPE_BYTES}
    # message -> [(field, number, type, repeated)]; XStat's values are a oneof
    schema = {
        "XSpace": [("planes", 1, "XPlane", True)],
        "XPlane": [("name", 2, "str", False), ("lines", 3, "XLine", True),
                   ("event_metadata", 4, "EventMetadataEntry", True),
                   ("stat_metadata", 5, "StatMetadataEntry", True)],
        "EventMetadataEntry": [("key", 1, "i64", False), ("value", 2, "XEventMetadata", False)],
        "StatMetadataEntry": [("key", 1, "i64", False), ("value", 2, "XStatMetadata", False)],
        "XLine": [("name", 2, "str", False), ("timestamp_ns", 3, "i64", False),
                  ("events", 4, "XEvent", True)],
        "XEvent": [("metadata_id", 1, "i64", False), ("offset_ps", 2, "i64", False),
                   ("duration_ps", 3, "i64", False), ("stats", 4, "XStat", True)],
        "XStat": [("metadata_id", 1, "u64", False), ("double_value", 2, "f64", False),
                  ("uint64_value", 3, "u64", False), ("int64_value", 4, "i64", False),
                  ("str_value", 5, "str", False), ("bytes_value", 6, "bytes", False),
                  ("ref_value", 7, "u64", False)],
        "XEventMetadata": [("name", 2, "str", False), ("stats", 5, "XStat", True)],
        "XStatMetadata": [("name", 2, "str", False)],
    }
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xspace_subset.proto", package="bench_xspace",
                                            syntax="proto3")
    for msg, fields in schema.items():
        m = fd.message_type.add(name=msg)
        if msg == "XStat":
            m.oneof_decl.add(name="value")
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if typ in scalar:
                f.type = scalar[typ]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xspace.{typ}"
            if msg == "XStat" and number > 1:
                f.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xspace.XSpace"))


def _stat_value(stat, names: dict):
    kind = stat.WhichOneof("value")
    if kind is None:
        return 0
    value = getattr(stat, kind)
    return names.get(value, "") if kind == "ref_value" else value


def _op_name(stats, ids: set, names: dict) -> str:
    """The op_name among ``stats`` (a stat whose id is in ``ids``), or ``""``."""
    return next((str(_stat_value(st, names)) for st in stats if st.metadata_id in ids), "")


def _stats(stats, names: dict) -> dict:
    return {names.get(s.metadata_id, ""): _stat_value(s, names) for s in stats}


def from_xspace(data: bytes, trace_mod) -> Program:
    """A :class:`Program` from a serialized XSpace."""
    space = _xspace_class().FromString(data)
    spans, scoped = [], {}
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = meta[ev.metadata_id].name if ev.metadata_id in meta else ""
                    if name.startswith(ROOT):
                        spans.append((_event(trace_mod, name, line, ev), _stats(ev.stats, names)))
        elif plane.name.startswith("/device:"):
            op_ids = {k for k, n in names.items() if n in OP_NAME_STATS}
            paths = {key: scope_path(_op_name(md.stats, op_ids, names)) for key, md in meta.items()}
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    # the event's own stat wins over its metadata's
                    own = _op_name(ev.stats, op_ids, names)
                    path = scope_path(own) if own else paths.get(ev.metadata_id, "")
                    if path:
                        name = meta[ev.metadata_id].name if ev.metadata_id in meta else ""
                        ops.append((_event(trace_mod, name, line, ev), path))
            if ops:
                scoped[plane.name] = ops
    spans.sort(key=lambda s: s[0].start)
    return Program(spans, scoped)


def _event(trace_mod, name: str, line, ev):
    start = line.timestamp_ns + ev.offset_ps // 1000
    return trace_mod.Event(trace_mod.short_name(name), start, start + ev.duration_ps // 1000,
                           name[:160], trace_mod.opcode(name))


def load(ctx) -> Program:
    """The run's :class:`Program`, parsed on first use and kept on
    ``ctx.trace``."""
    prog = getattr(ctx.trace, "program", None)
    if prog is None:
        files = sorted(glob.glob(str(Path(ctx.bench).parent / TRACE_DIR / "**" / "*.xplane.pb"), recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {TRACE_DIR}")
        prog = from_xspace(Path(files[-1]).read_bytes(), ctx.trace_mod)
        ctx.trace.program = prog
    return prog


# --- reductions ------------------------------------------------------------


def scoped_seconds(ctx, pick) -> float | None:
    """Device time of the window's ops whose scope path ``pick`` accepts
    (union of their intervals, loops and calls left out), averaged over
    the trace's devices; ``None`` if no op is scoped so."""
    prog, tr = load(ctx), ctx.trace
    lo, hi = tr.window
    total, found = 0, False
    for evs in prog.scoped.values():
        sel = [(e.start, e.end) for e, path in evs if pick(path) and not ctx.trace_mod.is_container(e.op)]
        found = found or bool(sel)
        total += sum(e - s for s, e in ctx.trace_mod.union(sel, lo, hi))
    if not found:
        return None
    return total / max(len(tr.ops), 1) * 1e-9


def innermost(spans: list) -> list[tuple[int, int, str]]:
    """``[(start, end, name)]``: the instants covered by ``spans``
    (``Event``s), each named by the innermost span open there, in order
    and disjoint."""
    marks = sorted([(e.start, 1, i) for i, e in enumerate(spans)] + [(e.end, 0, i) for i, e in enumerate(spans)])
    out, open_, last = [], set(), None
    for t, starts, i in marks:
        if open_ and t > last:
            inner = min(open_, key=lambda j: (spans[j].dur, -spans[j].start))
            out.append((last, t, spans[inner].name))
        (open_.add if starts else open_.discard)(i)
        last = t
    return out


def idle_under(ctx, spans: list, device: str) -> dict[str, int]:
    """Nanoseconds of ``device``'s idle time in the window under each
    innermost span of ``spans``."""
    tr = ctx.trace
    lo, hi = tr.window
    busy = ctx.trace_mod.union(((o.start, o.end) for o in tr.ops[device]), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    out: dict[str, int] = {}
    segs = innermost(spans)
    i = 0
    for gs, ge in gaps:
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < ge:
            s, e, name = segs[j]
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0:
                out[name] = out.get(name, 0) + overlap
            j += 1
    return out
