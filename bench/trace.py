"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per-device op intervals, program (module) executions and
the harness's host spans, all on the trace's one clock.

The traced window is the harness's own ``bench.window`` span.  Busy time
is the union of the intervals in which an op ran on a device, clipped to
that window; an idle gap is a stretch of the window with no op, named by
the innermost ``bench.*`` host span around its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "bench.window"
# XLA op names of the collectives, as the TPU trace spells them
COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "send", "recv",
)
# control flow whose event spans the ops of its body on the same line
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Event:
    name: str   # the HLO instruction's name, e.g. "psum_invariant.3"
    start: int  # ns, trace clock
    end: int
    label: str = ""  # the event's own text (the TPU trace gives the instruction)
    op: str = ""     # the HLO opcode, e.g. "all-reduce"

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    window: tuple[int, int]
    ops: dict[str, list[Event]]       # device plane -> op events
    modules: dict[str, list[Event]]   # device plane -> program executions
    spans: list[Event]                # host spans named bench.*

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def short_name(text: str) -> str:
    """``"%fusion.3 = bf16[..] fusion(..)"`` -> ``"fusion.3"``."""
    return text[1:].split(" = ", 1)[0] if text.startswith("%") else text


_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def opcode(text: str) -> str:
    """The opcode of an instruction's text (``"all-reduce"`` in
    ``"%psum.2 = f32[4]{0:T(128)} all-reduce(f32[4]{0} %p), ..."``: the
    first lower-case word before a bracket after ``=``; layouts' ``T(``
    and ``S(`` are upper case).  A bare name gives its stem."""
    if " = " in text:
        m = _OPCODE.search(text.split(" = ", 1)[1])
        if m:
            return m.group(1)
    return text.lstrip("%").split(".", 1)[0]


def _events(line):
    for e in line.events:
        start = int(e.start_ns)
        yield Event(short_name(e.name), start, start + int(e.duration_ns), e.name[:160], opcode(e.name))


def from_profile(pd) -> Trace:
    """Build a :class:`Trace` from a ``jax.profiler.ProfileData``."""
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = list(_events(line))
                elif line.name == "XLA Modules":
                    modules[plane.name] = list(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line) if e.name.startswith("bench."))
    wins = [s for s in spans if s.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"trace holds {len(wins)} {WINDOW_SPAN} spans, not 1")
    ops = {k: v for k, v in ops.items() if v}
    return Trace((wins[0].start, wins[0].end), ops, modules, spans)


def load(trace_dir: str | Path) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


# --- reductions ------------------------------------------------------------


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(tr: Trace, device: str) -> int:
    lo, hi = tr.window
    return sum(e - s for s, e in union(((o.start, o.end) for o in tr.ops[device]), lo, hi))


def busy_s(tr: Trace) -> float:
    """Seconds of the window in which an op ran, averaged over devices."""
    if not tr.ops:
        return 0.0
    return sum(busy_ns(tr, d) for d in tr.ops) / len(tr.ops) * 1e-9


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_s(tr) / tr.window_s


def op_seconds(tr: Trace, pick=lambda op: True) -> float:
    """Summed device time of the window's ops whose opcode ``pick``
    accepts, clipped to the window, averaged over devices."""
    if not tr.ops:
        return 0.0
    lo, hi = tr.window
    total = 0
    for evs in tr.ops.values():
        total += sum(min(e.end, hi) - max(e.start, lo) for e in evs
                     if pick(e.op) and e.end > lo and e.start < hi)
    return total / len(tr.ops) * 1e-9


def is_collective(op: str) -> bool:
    return op.startswith(COLLECTIVE_PREFIXES)


def is_container(op: str) -> bool:
    return op in CONTAINERS


def module_runs(tr: Trace, pick) -> tuple[int, float]:
    """Executions of the programs ``pick`` accepts that start in the
    window, and their device seconds, averaged over devices."""
    if not tr.modules:
        return 0, 0.0
    lo, hi = tr.window
    n, total = 0, 0
    for evs in tr.modules.values():
        sel = [e for e in evs if pick(e.name) and lo <= e.start < hi]
        n += len(sel)
        total += sum(e.dur for e in sel)
    k = len(tr.modules)
    return round(n / k), total / k * 1e-9


def top_ops(tr: Trace, k: int = 10) -> list[list]:
    """The ``k`` ops that took most device time in the window, each
    named by its instruction text (truncated); loops and calls, whose
    events span their bodies' ops, are left out."""
    lo, hi = tr.window
    acc: dict[str, int] = defaultdict(int)
    for evs in tr.ops.values():
        for e in evs:
            if e.end > lo and e.start < hi and not is_container(e.op):
                acc[e.label or e.name] += min(e.end, hi) - max(e.start, lo)
    n = max(len(tr.ops), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n * 1e-9] for name, ns in ranked]


def _span_at(tr: Trace, t: int) -> str:
    inner = None
    for s in tr.spans:
        if s.name != WINDOW_SPAN and s.start <= t < s.end:
            if inner is None or s.dur < inner.dur:
                inner = s
    return inner.name if inner else "bench.other"


def idle_gaps(tr: Trace, k: int = 10) -> list[list]:
    """The ``k`` longest idle stretches of the first device in the
    window, each named by the host span it fell in."""
    if not tr.ops:
        return []
    lo, hi = tr.window
    dev = sorted(tr.ops)[0]
    busy = union(((o.start, o.end) for o in tr.ops[dev]), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_span_at(tr, (s + e) // 2), (e - s) * 1e-9] for s, e in gaps[:k]]
