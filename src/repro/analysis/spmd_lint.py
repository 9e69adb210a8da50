"""SPMD jaxpr lint: prove the *executed* lowerings match the verified schedules.

The schedule verifier (:mod:`repro.analysis.schedule_verifier`) proves an
engine's *schedule object* correct and the HLO wire-lint
(:mod:`repro.analysis.hlo_lint`) checks the *compiled text* — this module
analyzes the layer in between: the traced jaxpr, the SPMD program we
actually run.  A dataflow walker recurses through ``pjit`` / ``shard_map``
/ ``scan`` / ``while`` / ``cond`` sub-jaxprs carrying, per value, the set
of mesh axes it may *vary* over, and proves four rule families:

1. **collective-uniformity** — every collective primitive (``psum``,
   ``ppermute``, ``all_to_all``, ``all_gather``, ``reduce_scatter``,
   transport ``pallas_call``) is reached uniformly across ranks: never
   under a ``cond``/``while`` predicate whose dataflow cone includes
   rank-varying values (``axis_index``, un-reduced device data).  A
   collective some group members skip deadlocks even when its schedule
   is a proven DAG — this is the static hang detector.
2. **axis-discipline** — collective axis names resolve against the
   declared topology axes, nested ``shard_map`` never shadows a bound
   axis, and the per-axis collective *sequence* is structurally
   identical on every path through branching control flow (the executed
   counterpart of the schedule verifier's deadlock invariant).
3. **numerics-flow** — no silent precision demotion on reduction paths:
   sub-f32 floats must not be sum-reduced across the slow domain
   (``psum``/``psum_scatter`` over an inter axis) or folded (``add`` /
   ``reduce_sum``) straight off an inter-node exchange without an f32
   upcast; quantize transport kernels must be dominated by a measured
   scale computation (``abs``/``max``/``pmax`` ancestry); packed wire
   words must stay within the kernel's declared width.
4. **byte-accounting** — per-collective inter-node bytes are recomputed
   from jaxpr shapes x replica groups (node-major chip enumeration,
   exactly :func:`repro.core.collectives._chip_index`'s layout) and
   compared against the schedule verifier's declared bound, closing the
   proof chain *schedule -> jaxpr -> HLO*.

Plus **alias-donation**: a transport ``pallas_call`` whose name declares
a donated operand (``...__donate<i>``, see
:mod:`repro.kernels.transport`) must never have that operand read again
after the call.

Entry points: :func:`lint_jaxpr` over a ``jax.make_jaxpr`` result, or
the :func:`lint_traced` convenience that traces for you.  Engine-level
integration lives in :func:`repro.core.comm.lint_lowering` (run at
registration for every engine — including the natives that opt out of
schedule verification, which have no schedule to verify but very much
have a jaxpr to lint).

This module imports ``jax`` only inside functions (package rule: the
registry calls *into* the analyzers, and ``__main__`` must set
``XLA_FLAGS`` before anything pulls in jax).
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

__all__ = [
    "SPMD_RULES",
    "COLLECTIVE_PRIMS",
    "SpmdViolation",
    "SpmdLintReport",
    "lint_jaxpr",
    "lint_traced",
    "assert_spmd_clean",
]

#: the four rule families (+ the donation rule) with one-line contracts
SPMD_RULES = {
    "collective-uniformity": (
        "no collective under a rank-varying cond/while predicate"
    ),
    "axis-discipline": (
        "collective axes resolve, are never shadowed, and the collective "
        "sequence is identical on every control-flow path"
    ),
    "numerics-flow": (
        "no sub-f32 accumulation across the slow domain; quantize scales "
        "are measured; wire words stay within declared width"
    ),
    "byte-accounting": (
        "jaxpr-recomputed inter-node bytes equal the declared bound"
    ),
    "alias-donation": (
        "a donated pallas operand is never read after the call"
    ),
}

#: jaxpr primitives that move data between devices
COLLECTIVE_PRIMS = frozenset(
    {"psum", "pmax", "pmin", "ppermute", "all_to_all", "all_gather",
     "reduce_scatter"}
)

# sum-semantics reductions (pmax/pmin lose nothing to low precision)
_SUM_REDUCING = frozenset({"psum", "reduce_scatter"})
# local sum-fold primitives the f32-accumulation rule watches
_FOLD_PRIMS = frozenset({"add", "add_any", "reduce_sum"})
# primitives that seed scale provenance (max-abs scale computations)
_SCALE_SEEDS = frozenset(
    {"abs", "max", "min", "reduce_max", "reduce_min", "pmax", "pmin"}
)
#: pallas transport kernel name prefixes (repro.kernels.transport)
_TRANSPORT_PREFIXES = ("quantize_pack", "unpack_dequantize")
_DONATE_RE = re.compile(r"__donate(\d+)")
#: shape/type-only ops a kernel wrapper may put between the caller's
#: buffer and the kernel operand: donating the view donates the source
_VIEW_PRIMS = frozenset({"reshape", "squeeze", "expand_dims", "pvary", "pcast"})
_BITS_RE = re.compile(r"^(?:quantize_pack|unpack_dequantize)_(\d+)b")

_REL_TOL = 1e-6  # byte-accounting comparison tolerance (relative)


@dataclasses.dataclass(frozen=True)
class SpmdViolation:
    """One SPMD lint rule violation."""

    rule: str
    message: str

    def to_row(self) -> dict:
        return {"rule": self.rule, "message": self.message}


@dataclasses.dataclass
class SpmdLintReport:
    """Result of linting one traced program."""

    label: str
    violations: list = dataclasses.field(default_factory=list)
    collectives: int = 0
    internode_bytes_per_chip: float | None = None
    declared_bytes: tuple | None = None
    notes: list = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_row(self) -> dict:
        return {
            "label": self.label,
            "ok": self.ok,
            "collectives": self.collectives,
            "internode_bytes_per_chip": self.internode_bytes_per_chip,
            "declared_bytes": (
                list(self.declared_bytes)
                if self.declared_bytes is not None
                else None
            ),
            "notes": list(self.notes),
            "violations": [v.to_row() for v in self.violations],
        }


# ---------------------------------------------------------------------------
# per-value dataflow state
# ---------------------------------------------------------------------------


class _St:
    """Lattice state of one jaxpr value.

    var:   axis names the value may vary over (rank variance).
    scale: has max-abs/reduce ancestry (quantize scale provenance).
    wire:  packed wire bytes produced by a quantize transport kernel.
    net:   crossed the slow domain without re-accumulation (f32 upcast
           or an actual reduction clears it).
    """

    __slots__ = ("var", "scale", "wire", "net")

    def __init__(self, var=frozenset(), scale=False, wire=False, net=False):
        self.var = frozenset(var)
        self.scale = bool(scale)
        self.wire = bool(wire)
        self.net = bool(net)

    def join(self, other: "_St") -> "_St":
        return _St(
            self.var | other.var,
            self.scale or other.scale,
            self.wire or other.wire,
            self.net or other.net,
        )

    def __eq__(self, other):
        return (
            isinstance(other, _St)
            and self.var == other.var
            and self.scale == other.scale
            and self.wire == other.wire
            and self.net == other.net
        )

    def __hash__(self):
        return hash((self.var, self.scale, self.wire, self.net))


_BOTTOM = _St()


def _join_all(states) -> _St:
    out = _BOTTOM
    for s in states:
        out = out.join(s)
    return out


def _axes_of(params) -> tuple[str, ...]:
    """Named axes of a collective eqn (positional int axes ignored)."""
    raw = params.get("axes", params.get("axis_name", ()))
    if isinstance(raw, str):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def _pallas_name(params) -> str:
    """The user-visible kernel name of a ``pallas_call`` eqn."""
    info = params.get("name_and_src_info")
    if info is None:
        return str(params.get("name", ""))
    text = str(info)
    # "myname for kernel function _k at /p.py:1" or "_k at /p.py:1"
    return text.split(" for ")[0].split(" at ")[0].strip()


def _is_transport(name: str) -> bool:
    return name.startswith(_TRANSPORT_PREFIXES)


def _sub_f32_float(dtype) -> bool:
    """A float dtype narrower than float32 (accumulation hazard)."""
    name = str(dtype)
    return name in ("bfloat16", "float16") or name.startswith("float8")


def _wide_int(dtype) -> bool:
    name = str(dtype)
    return name in ("int16", "uint16", "int32", "uint32", "int64", "uint64")


def _aval_bytes(atom) -> float:
    aval = atom.aval
    elems = 1
    for d in aval.shape:
        elems *= int(d)
    return float(elems) * np.dtype(aval.dtype).itemsize


# ---------------------------------------------------------------------------
# collective signature (branch-symmetry rule)
# ---------------------------------------------------------------------------


def _signature(jaxpr) -> tuple:
    """Structural collective sequence of an (open) jaxpr."""
    out = []
    for eqn in jaxpr.eqns:
        p = eqn.primitive.name
        if p in COLLECTIVE_PRIMS:
            out.append((p, tuple(sorted(_axes_of(eqn.params)))))
        elif p == "cond":
            out.append(
                ("cond",)
                + tuple(_signature(b.jaxpr) for b in eqn.params["branches"])
            )
        elif p == "while":
            out.append(
                (
                    "while",
                    _signature(eqn.params["cond_jaxpr"].jaxpr),
                    _signature(eqn.params["body_jaxpr"].jaxpr),
                )
            )
        elif p == "scan":
            out.append(
                (
                    "scan",
                    int(eqn.params["length"]),
                    _signature(eqn.params["jaxpr"].jaxpr),
                )
            )
        elif p in ("pjit", "closed_call", "custom_jvp_call",
                   "custom_vjp_call", "remat", "checkpoint"):
            sub = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            if sub is not None:
                out.extend(_signature(getattr(sub, "jaxpr", sub)))
        elif p == "shard_map":
            out.append(("shard_map", _signature(eqn.params["jaxpr"])))
        elif p == "pallas_call":
            name = _pallas_name(eqn.params)
            if _is_transport(name):
                out.append(("pallas", name))
    return tuple(out)


# ---------------------------------------------------------------------------
# the analyzer
# ---------------------------------------------------------------------------


class _Analyzer:
    def __init__(self, axis_sizes, inter_axes, intra_axes, declared, *,
                 bind_root=True):
        # ``sizes`` is the topology universe (byte accounting); ``bound``
        # is the axes *in scope* at the current program point (shadowing
        # and axis resolution).  They differ for a mesh-level program
        # traced without an axis env: the topology axes exist, but only
        # the program's own shard_map brings them into scope.
        self.sizes = dict(axis_sizes)
        self.bound = dict(axis_sizes) if bind_root else {}
        self.inter = frozenset(inter_axes)
        self.intra = frozenset(intra_axes)
        self.violations: list[SpmdViolation] = []
        self.notes: list[str] = []
        self.collectives = 0
        self.declared = declared
        # byte accounting: node-major chip universe over the topology
        # axes (inter-major), matching collectives._chip_index
        self.track_bytes = bool(inter_axes)
        self.bytes_unknown = False
        order = tuple(inter_axes) + tuple(intra_axes)
        if self.track_bytes and not all(a in self.sizes for a in order):
            self.track_bytes = False
            self.notes.append("topology axes unbound: bytes not tracked")
        self.axis_order = order
        if self.track_bytes:
            sizes = [self.sizes[a] for a in order]
            self.n_chips = int(np.prod(sizes)) if sizes else 1
            self.ppn = int(
                np.prod([self.sizes[a] for a in intra_axes])
            ) if intra_axes else 1
            coords = []
            for c in range(self.n_chips):
                rem, cc = c, {}
                for a in reversed(order):
                    cc[a] = rem % self.sizes[a]
                    rem //= self.sizes[a]
                coords.append(cc)
            self.coords = coords
            self.sends = np.zeros(self.n_chips, dtype=np.float64)

    # -- violation helpers -------------------------------------------------

    def _flag(self, rule: str, message: str) -> None:
        self.violations.append(SpmdViolation(rule, message))

    # -- byte accounting ---------------------------------------------------

    def _account(self, prim, axes, eqn, mult) -> None:
        if not self.track_bytes:
            return
        if not set(axes) & self.inter:
            return  # intra-node traffic is free at this accounting layer
        b = sum(_aval_bytes(a) for a in eqn.invars if hasattr(a, "aval"))
        if b == 0.0:
            return
        if mult is None:
            self.bytes_unknown = True
            self.notes.append(
                f"{prim} inside a while body: inter-node bytes unbounded"
            )
            return
        if not all(a in self.axis_order for a in axes) or eqn.params.get(
            "axis_index_groups"
        ):
            self.bytes_unknown = True
            self.notes.append(
                f"{prim} over non-topology axes/index groups: not modeled"
            )
            return
        # build groups: chips agreeing on every non-collective axis
        others = [a for a in self.axis_order if a not in axes]
        groups: dict[tuple, list] = {}
        for c in range(self.n_chips):
            cc = self.coords[c]
            key = tuple(cc[o] for o in others)
            m = 0
            for a in axes:
                m = m * self.sizes[a] + cc[a]
            groups.setdefault(key, []).append((m, c))
        node = lambda c: c // self.ppn  # noqa: E731
        perm = eqn.params.get("perm", ())
        for members in groups.values():
            members.sort()
            mem = [c for _, c in members]
            g = len(mem)
            if prim == "ppermute":
                for (s, d) in perm:
                    if s != d and node(mem[s]) != node(mem[d]):
                        self.sends[mem[s]] += b * mult
                continue
            for i, c in enumerate(mem):
                cross = sum(
                    1 for j, c2 in enumerate(mem)
                    if j != i and node(c2) != node(c)
                )
                if prim in ("psum", "pmax", "pmin"):
                    self.sends[c] += 2.0 * b / g * cross * mult
                elif prim in ("reduce_scatter", "all_to_all"):
                    self.sends[c] += b / g * cross * mult
                elif prim == "all_gather":
                    self.sends[c] += b * cross * mult

    # -- walker ------------------------------------------------------------

    def run(self, closed, in_states):
        consts = {
            v: _BOTTOM for v in closed.jaxpr.constvars
        }
        env = dict(consts)
        for v, s in zip(closed.jaxpr.invars, in_states):
            env[v] = s
        return self._walk(
            closed.jaxpr, env, ctx=frozenset(), mult=1, record=True
        )

    def _state(self, env, atom) -> _St:
        if hasattr(atom, "val"):  # Literal
            return _BOTTOM
        return env.get(atom, _BOTTOM)

    def _walk(self, jaxpr, env, ctx, mult, record):
        for eqn in jaxpr.eqns:
            p = eqn.primitive.name
            ins = [self._state(env, a) for a in eqn.invars]
            if p in COLLECTIVE_PRIMS:
                outs = self._collective(eqn, p, ins, ctx, mult, record)
            elif p == "axis_index":
                ax = eqn.params.get("axis_name")
                outs = [_St(var={ax} if isinstance(ax, str) else set())]
            elif p == "cond":
                outs = self._cond(eqn, ins, ctx, mult, record)
            elif p == "while":
                outs = self._while(eqn, ins, ctx, mult, record)
            elif p == "scan":
                outs = self._scan(eqn, ins, ctx, mult, record)
            elif p in ("pjit", "closed_call", "core_call", "remat",
                       "checkpoint", "custom_jvp_call", "custom_vjp_call"):
                outs = self._call(eqn, ins, ctx, mult, record)
            elif p == "shard_map":
                outs = self._shard_map(eqn, ins, ctx, mult, record)
            elif p == "pallas_call":
                outs = self._pallas(eqn, jaxpr, ins, ctx, record)
            elif p == "convert_element_type":
                j = _join_all(ins)
                # an f32/f64 upcast legalizes downstream accumulation
                wide = str(eqn.params.get("new_dtype")) in (
                    "float32", "float64"
                )
                outs = [_St(j.var, j.scale, j.wire, j.net and not wide)]
            else:
                j = _join_all(ins)
                if p in _FOLD_PRIMS and record:
                    self._check_fold(eqn, p, ins, j)
                if p in _SCALE_SEEDS:
                    j = _St(j.var, True, j.wire, j.net)
                outs = [j] * len(eqn.outvars)
            if len(outs) != len(eqn.outvars):
                outs = [_join_all(outs)] * len(eqn.outvars)
            for v, s in zip(eqn.outvars, outs):
                env[v] = s
        return [self._state(env, a) for a in jaxpr.outvars]

    # -- rule checks at specific primitives --------------------------------

    def _check_fold(self, eqn, p, ins, joined):
        out_dtype = eqn.outvars[0].aval.dtype
        if any(s.net for s in ins) and _sub_f32_float(out_dtype):
            self._flag(
                "numerics-flow",
                f"{p} folds an inter-node exchanged value in "
                f"{out_dtype} without an f32 upcast (accumulation must "
                "be float32 across the slow domain)",
            )

    def _collective(self, eqn, p, ins, ctx, mult, record):
        axes = _axes_of(eqn.params)
        if record:
            self.collectives += 1
            unknown = [a for a in axes if a not in self.bound]
            if unknown:
                self._flag(
                    "axis-discipline",
                    f"{p} names unbound axes {unknown}; declared axes: "
                    f"{sorted(self.bound)}",
                )
            hang = set(axes) & ctx
            if hang:
                self._flag(
                    "collective-uniformity",
                    f"{p} over {axes} sits under a predicate that varies "
                    f"over {sorted(hang)}: group members may disagree on "
                    "reaching it (static hang)",
                )
            if p in _SUM_REDUCING and set(axes) & self.inter:
                for a in eqn.invars:
                    if hasattr(a, "aval") and _sub_f32_float(a.aval.dtype):
                        self._flag(
                            "numerics-flow",
                            f"{p} over inter axes {axes} sum-reduces a "
                            f"{a.aval.dtype} payload: cross-node "
                            "accumulation must be float32",
                        )
            for a, s in zip(eqn.invars, ins):
                if s.wire and hasattr(a, "aval") and _wide_int(a.aval.dtype):
                    self._flag(
                        "numerics-flow",
                        f"{p} moves a {a.aval.dtype} value carrying packed "
                        "wire words: exceeds the declared wire width",
                    )
            self._account(p, axes, eqn, mult)
        j = _join_all(ins)
        crosses = bool(set(axes) & self.inter)
        if p in ("psum", "pmax", "pmin"):
            # reduced over axes: uniform there, and the reduction itself
            # re-accumulated whatever crossed the wire
            out = _St(j.var - set(axes), j.scale, j.wire, False)
        elif p == "all_gather":
            # gathered: uniform over axes, but copies crossed un-reduced
            out = _St(j.var - set(axes), j.scale, j.wire, j.net or crosses)
        else:  # ppermute, all_to_all, reduce_scatter: position-dependent
            net = False if p == "reduce_scatter" else (j.net or crosses)
            out = _St(j.var | set(axes), j.scale, j.wire, net)
        return [out] * len(eqn.outvars)

    def _cond(self, eqn, ins, ctx, mult, record):
        branches = eqn.params["branches"]
        pred = ins[0]
        if record and len(branches) > 1:
            sigs = {_signature(b.jaxpr) for b in branches}
            if len(sigs) > 1:
                self._flag(
                    "axis-discipline",
                    "cond branches execute different collective "
                    "sequences: "
                    + " vs ".join(str(s) for s in sorted(sigs)),
                )
        sub_ctx = ctx | pred.var
        outs = None
        for b in branches:
            env = {v: _BOTTOM for v in b.jaxpr.constvars}
            for v, s in zip(b.jaxpr.invars, ins[1:]):
                env[v] = s
            res = self._walk(b.jaxpr, env, sub_ctx, mult, record)
            outs = res if outs is None else [
                a.join(bb) for a, bb in zip(outs, res)
            ]
        # branch outputs data-depend on the predicate
        return [_St(s.var | pred.var, s.scale, s.wire, s.net) for s in outs]

    def _run_closed(self, closed, in_states, ctx, mult, record):
        env = {v: _BOTTOM for v in closed.jaxpr.constvars}
        for v, s in zip(closed.jaxpr.invars, in_states):
            env[v] = s
        return self._walk(closed.jaxpr, env, ctx, mult, record)

    def _while(self, eqn, ins, ctx, mult, record):
        P = eqn.params
        cj, bj = P["cond_jaxpr"], P["body_jaxpr"]
        nc, nb = P["cond_nconsts"], P["body_nconsts"]
        cconsts, bconsts = ins[:nc], ins[nc:nc + nb]
        carry = list(ins[nc + nb:])
        for _ in range(len(self.bound) + 3):
            pred = self._run_closed(
                cj, cconsts + carry, ctx, mult, False
            )[0]
            new = self._run_closed(
                bj, bconsts + carry, ctx | pred.var, mult, False
            )
            nxt = [a.join(b) for a, b in zip(carry, new)]
            if nxt == carry:
                break
            carry = nxt
        pred = self._run_closed(cj, cconsts + carry, ctx, mult, False)[0]
        if record:
            self._run_closed(cj, cconsts + carry, ctx | pred.var, None, True)
            self._run_closed(
                bj, bconsts + carry, ctx | pred.var, None, True
            )
        return [
            _St(s.var | pred.var, s.scale, s.wire, s.net) for s in carry
        ]

    def _scan(self, eqn, ins, ctx, mult, record):
        P = eqn.params
        closed = P["jaxpr"]
        nc, ncarry = P["num_consts"], P["num_carry"]
        length = int(P["length"])
        consts = ins[:nc]
        carry = list(ins[nc:nc + ncarry])
        xs = ins[nc + ncarry:]
        ys = None
        for _ in range(len(self.bound) + 3):
            res = self._run_closed(
                closed, consts + carry + xs, ctx, mult, False
            )
            new_carry = [
                a.join(b) for a, b in zip(carry, res[:ncarry])
            ]
            ys = res[ncarry:]
            if new_carry == carry:
                break
            carry = new_carry
        if record:
            m = None if mult is None else mult * length
            self._run_closed(closed, consts + carry + xs, ctx, m, True)
        return carry + list(ys)

    def _call(self, eqn, ins, ctx, mult, record):
        sub = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
        if sub is None:
            j = _join_all(ins)
            return [j] * len(eqn.outvars)
        if hasattr(sub, "consts"):  # ClosedJaxpr
            return self._run_closed(sub, ins, ctx, mult, record)
        env = {v: _BOTTOM for v in getattr(sub, "constvars", ())}
        for v, s in zip(sub.invars, ins):
            env[v] = s
        return self._walk(sub, env, ctx, mult, record)

    def _shard_map(self, eqn, ins, ctx, mult, record):
        mesh = eqn.params.get("mesh")
        try:
            sizes = dict(mesh.shape)
        except Exception:
            sizes = {}
        if record:
            shadow = sorted(set(sizes) & set(self.bound))
            if shadow:
                self._flag(
                    "axis-discipline",
                    f"shard_map re-binds already-bound axes {shadow} "
                    "(axis shadowing)",
                )
        saved = dict(self.bound)
        self.bound.update(sizes)
        sub = eqn.params["jaxpr"]  # open jaxpr
        in_names = eqn.params.get("in_names", ())

        def _split_axes(entry):
            found = set()
            stack = [entry]
            while stack:
                e = stack.pop()
                if isinstance(e, str):
                    found.add(e)
                elif isinstance(e, dict):
                    stack.extend(e.values())
                elif isinstance(e, (tuple, list, frozenset, set)):
                    stack.extend(e)
            return found

        env = {v: _BOTTOM for v in getattr(sub, "constvars", ())}
        for i, (v, s) in enumerate(zip(sub.invars, ins)):
            split = (
                _split_axes(in_names[i]) if i < len(in_names) else set(sizes)
            )
            env[v] = _St(s.var | split, s.scale, s.wire, s.net)
        try:
            outs = self._walk(sub, env, ctx, mult, record)
        finally:
            self.bound = saved
        return outs

    def _pallas(self, eqn, parent, ins, ctx, record):
        name = _pallas_name(eqn.params)
        transport = _is_transport(name)
        j = _join_all(ins)
        if transport and record:
            if ctx:
                self._flag(
                    "collective-uniformity",
                    f"transport kernel {name!r} sits under a predicate "
                    f"that varies over {sorted(ctx)}: its paired "
                    "collective cannot be reached uniformly",
                )
            m = _BITS_RE.match(name)
            if name.startswith("quantize_pack"):
                # scale provenance: operand 1 is the (1, L) scale vector
                if len(ins) > 1 and not ins[1].scale:
                    self._flag(
                        "numerics-flow",
                        f"quantize kernel {name!r}: scales operand has "
                        "no max-abs ancestry (undominated scale)",
                    )
                out_dtype = eqn.outvars[0].aval.dtype
                if m is not None:
                    bits = int(m.group(1))
                    want = "uint8" if bits == 4 else "int8"
                    if str(out_dtype) != want:
                        self._flag(
                            "numerics-flow",
                            f"{name!r} emits {out_dtype} wire words; "
                            f"{bits}-bit transport declares {want}",
                        )
                elif np.dtype(out_dtype).itemsize != 1:
                    self._flag(
                        "numerics-flow",
                        f"{name!r} emits {out_dtype} wire words "
                        "(wider than one byte)",
                    )
            # donation: the declared operand must be dead after the call
            for d in _DONATE_RE.findall(name):
                idx = int(d)
                if idx >= len(eqn.invars):
                    continue
                donated = eqn.invars[idx]
                if hasattr(donated, "val"):
                    continue  # literal
                self._check_dead_after(parent, eqn, donated, name)
        if transport and name.startswith("quantize_pack"):
            out = _St(j.var, False, True, False)
        elif transport:
            out = _St(j.var, j.scale, False, False)
        else:
            out = j
        return [out] * len(eqn.outvars)

    def _check_dead_after(self, jaxpr, call_eqn, var, name):
        # the donated operand and every buffer it is a view of
        chain = [var]
        for eqn in reversed(jaxpr.eqns[: jaxpr.eqns.index(call_eqn)]):
            if (
                eqn.primitive.name in _VIEW_PRIMS
                and any(o is chain[-1] for o in eqn.outvars)
                and not hasattr(eqn.invars[0], "val")
            ):
                chain.append(eqn.invars[0])
        seen = False
        for eqn in jaxpr.eqns:
            if eqn is call_eqn:
                seen = True
                continue
            if seen and any(a is v for a in eqn.invars for v in chain):
                self._flag(
                    "alias-donation",
                    f"{name!r} declares operand donation but the donated "
                    f"buffer is read again by {eqn.primitive.name} after "
                    "the call (alias hazard)",
                )
                return
        if any(a is v for a in jaxpr.outvars for v in chain):
            self._flag(
                "alias-donation",
                f"{name!r} declares operand donation but the donated "
                "buffer is returned as an output (alias hazard)",
            )


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def lint_jaxpr(
    closed,
    *,
    axis_sizes: dict,
    inter_axes=(),
    intra_axes=(),
    declared_internode_bytes=None,
    label: str = "",
    axes_bound_at_root: bool = True,
) -> SpmdLintReport:
    """Lint one ``ClosedJaxpr`` (a ``jax.make_jaxpr`` result).

    ``axis_sizes`` declares the topology axes and their sizes;
    ``inter_axes`` names the slow domain for the numerics and byte
    rules.  ``declared_internode_bytes`` — a float or ``(lo, hi)``
    range of per-chip inter-node bytes — switches byte accounting on;
    the recomputed maximum must land inside it.

    ``axes_bound_at_root`` says whether the jaxpr was traced *under*
    that axis environment (``jax.make_jaxpr(..., axis_env=...)`` — the
    per-shard view, inputs vary over every axis, a nested ``shard_map``
    over the same names is shadowing) or is a mesh-level program whose
    own inner ``shard_map`` brings the axes into scope for the first
    time (pass ``False``: inputs start uniform — they are host-level
    values — and the first binding is not shadowing).
    """
    inter = (inter_axes,) if isinstance(inter_axes, str) else tuple(inter_axes)
    intra = (intra_axes,) if isinstance(intra_axes, str) else tuple(intra_axes)
    declared = declared_internode_bytes
    if declared is not None and not isinstance(declared, (tuple, list)):
        declared = (float(declared), float(declared))
    a = _Analyzer(axis_sizes, inter, intra, declared,
                  bind_root=axes_bound_at_root)
    jaxpr = closed.jaxpr
    in_states = [
        _St(var=set(axis_sizes) if axes_bound_at_root else frozenset())
        for _ in jaxpr.invars
    ]  # per-shard trace: device data varies over every declared axis;
    #    mesh-level trace: inputs are host values, uniform until sharded
    a.run(closed, in_states)
    report = SpmdLintReport(label=label or "jaxpr")
    report.violations = a.violations
    report.notes = a.notes
    report.collectives = a.collectives
    if a.track_bytes and not a.bytes_unknown:
        got = float(a.sends.max(initial=0.0))
        report.internode_bytes_per_chip = got
        if declared is not None:
            report.declared_bytes = declared
            lo, hi = declared
            tol = _REL_TOL * max(1.0, hi)
            if not (lo - tol <= got <= hi + tol):
                report.violations.append(
                    SpmdViolation(
                        "byte-accounting",
                        f"jaxpr-recomputed inter-node bytes/chip "
                        f"{got:.1f} outside the declared bound "
                        f"[{lo:.1f}, {hi:.1f}]",
                    )
                )
    return report


def lint_traced(
    fn,
    *example_args,
    axis_env=(),
    inter_axes=(),
    intra_axes=(),
    declared_internode_bytes=None,
    label: str = "",
) -> SpmdLintReport:
    """Trace ``fn`` under ``axis_env`` (``[(name, size), ...]``) and lint.

    ``example_args`` may be arrays or ``jax.ShapeDtypeStruct``s — the
    trace is abstract either way.  This is the convenience the tests and
    the ``--spmd`` sweep use; :func:`repro.core.comm.lint_lowering`
    wraps it per registered engine with the schedule-declared byte
    bound filled in.
    """
    import jax

    axis_env = list(axis_env)
    closed = jax.make_jaxpr(fn, axis_env=axis_env or None)(*example_args)
    return lint_jaxpr(
        closed,
        axis_sizes=dict(axis_env),
        inter_axes=inter_axes,
        intra_axes=intra_axes,
        declared_internode_bytes=declared_internode_bytes,
        label=label,
    )


def assert_spmd_clean(report: SpmdLintReport) -> None:
    """Raise ``AssertionError`` listing every violation (test helper)."""
    if not report.ok:
        raise AssertionError(
            f"{report.label}: {len(report.violations)} SPMD lint "
            "violation(s):\n"
            + "\n".join(
                f"  [{v.rule}] {v.message}" for v in report.violations
            )
        )
