"""Topology-first collective API: ``Topology`` + engine registry + ``CommContext``.

This module is the public face of the allreduce stack.  The paper's whole
point is that collective dispatch is a function of *machine topology* —
node count, lanes per node, intra/inter link rates — so topology is a
first-class, frozen, hashable object here instead of loose
``(inter_axes, intra_axes, n, ppn, params)`` keyword soup:

* :class:`Topology` owns the grid shape, the mesh axis names and the
  :class:`~repro.core.perf_model.MachineParams`, and memoises every
  derived quantity (NAP↔MLA crossover, schedules, ragged chunk geometry,
  inter-node lower bounds) so no module ever re-derives or re-defaults
  them;
* the **engine registry** (:func:`register_engine` /
  :func:`select_engine`) replaces the old ``ALGORITHMS`` dict and the
  ``_MLA_OPS`` / ``_LARGE_COSTS`` side tables: an engine is one
  declaration carrying its capabilities (ops, grid constraints), its
  cost model and its executable lowering, and dispatch is a
  capability-filtered cost tournament over the registered engines;
* :class:`CommContext` is the facade: ``allreduce``, ``reduce_scatter``
  and ``allgather`` are peer public collectives (RS/AG promoted from MLA
  internals — ZeRO-style sharded-optimizer sync is expressible), plus
  bucket-scheduled gradient sync.

Quickstart — mesh to collective in a few lines::

    from repro.core import comm
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=True)   # 2 pods x 16 x 16
    topo = comm.Topology.from_mesh(mesh)          # n=2, ppn=256, params
    ctx = comm.CommContext(topo)                  # default auto policy
    sync = jax.shard_map(
        lambda g: ctx.allreduce(g), mesh=mesh,
        in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
    )                                             # model-driven dispatch

The deprecated entry points (``collectives.hierarchical_allreduce``,
``grad_sync.GradSyncConfig``) are thin shims over this module: they build
a ``Topology`` + default policy internally and warn (once) on first use.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import types
import warnings
from typing import Callable, NamedTuple

import jax
import numpy as np

from . import collectives, napalg, perf_model as pm
from ..runtime.tracing import scope

__all__ = [
    "Topology",
    "EngineSpec",
    "Decision",
    "register_engine",
    "get_engine",
    "registered_engines",
    "find_engine",
    "engine_schedule",
    "verify_engine",
    "lint_lowering",
    "select_engine",
    "CommPolicy",
    "CommContext",
    "COLLECTIVES",
    "warn_deprecated_once",
]

#: the collective families the registry dispatches over
COLLECTIVES = ("allreduce", "reduce_scatter", "allgather")


# ---------------------------------------------------------------------------
# Topology
# ---------------------------------------------------------------------------


def _axes_tuple(axes) -> tuple[str, ...]:
    if axes is None:
        return ()
    return (axes,) if isinstance(axes, str) else tuple(axes)


@dataclasses.dataclass(frozen=True)
class Topology:
    """Frozen, hashable description of a two-level device grid.

    ``n_nodes`` nodes (pods — the slow domain) of ``ppn`` chips each,
    optionally bound to mesh axis names so collectives can execute, plus
    the machine constants every cost decision is solved under.  Being
    hashable, a Topology keys every ``lru_cache`` in the stack — equal
    topologies share schedules, crossovers and bucket plans.
    """

    n_nodes: int
    ppn: int
    inter_axes: tuple[str, ...] = ()
    intra_axes: tuple[str, ...] = ()
    params: pm.MachineParams = pm.TPU_V5E_POD

    def __post_init__(self):
        object.__setattr__(self, "inter_axes", _axes_tuple(self.inter_axes))
        object.__setattr__(self, "intra_axes", _axes_tuple(self.intra_axes))
        if self.n_nodes < 1 or self.ppn < 1:
            raise ValueError(
                f"topology needs n_nodes >= 1 and ppn >= 1, got "
                f"({self.n_nodes}, {self.ppn})"
            )

    # -- constructors ------------------------------------------------------

    @classmethod
    def of(
        cls, n_nodes: int, ppn: int, *, params: pm.MachineParams | None = None
    ) -> "Topology":
        """Explicit grid shape, no axis binding (planning/analysis use)."""
        return cls(
            int(n_nodes), int(ppn), params=params or pm.TPU_V5E_POD
        )

    @classmethod
    def from_mesh(
        cls,
        mesh,
        *,
        inter_axes=None,
        intra_axes=None,
        params: pm.MachineParams | None = None,
    ) -> "Topology":
        """Topology of a jax mesh (host-side; no traced context needed).

        Axis defaults follow :func:`repro.launch.mesh.hierarchy_axes`:
        a ``"pod"`` axis is the slow domain, everything else data-local.
        """
        if inter_axes is None or intra_axes is None:
            from ..launch.mesh import hierarchy_axes

            d_inter, d_intra = hierarchy_axes(mesh)
            if inter_axes is None:
                inter_axes = d_inter
            if intra_axes is None:
                intra_axes = d_intra
        inter = _axes_tuple(inter_axes)
        intra = _axes_tuple(intra_axes)
        overlap = set(inter) & set(intra)
        if overlap:
            raise ValueError(
                f"axes {sorted(overlap)} appear in both inter_axes "
                f"{inter} and intra_axes {intra}"
            )
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        for ax in inter + intra:
            if ax not in sizes:
                raise ValueError(
                    f"axis {ax!r} not in mesh axes {tuple(sizes)}"
                )
        n = int(np.prod([sizes[a] for a in inter])) if inter else 1
        ppn = int(np.prod([sizes[a] for a in intra])) if intra else 1
        return cls(
            n, ppn, inter_axes=inter, intra_axes=intra,
            params=params or pm.TPU_V5E_POD,
        )

    @classmethod
    def from_axes(
        cls,
        inter_axes,
        intra_axes,
        *,
        params: pm.MachineParams | None = None,
    ) -> "Topology":
        """Topology from named mesh axes, *inside* a traced context
        (axis sizes come from ``jax.lax``/shard_map)."""
        inter = _axes_tuple(inter_axes)
        intra = _axes_tuple(intra_axes)
        n = int(np.prod([jax.lax.axis_size(a) for a in inter])) if inter else 1
        ppn = (
            int(np.prod([jax.lax.axis_size(a) for a in intra])) if intra else 1
        )
        return cls(
            n, ppn, inter_axes=inter, intra_axes=intra,
            params=params or pm.TPU_V5E_POD,
        )

    # -- basic shape -------------------------------------------------------

    @property
    def group(self) -> int:
        """Total chips — the reduction group size."""
        return self.n_nodes * self.ppn

    @property
    def has_slow_domain(self) -> bool:
        return self.n_nodes > 1

    @property
    def axes(self) -> tuple[str, ...]:
        """Joint (inter + intra) axis names, slow-domain-major."""
        return self.inter_axes + self.intra_axes

    def require_axes(self) -> "Topology":
        """Guard for execution entry points (returns ``self``).

        A multi-chip topology without mesh axis names (``Topology.of``
        — the planning/analysis constructor) cannot execute: the
        collectives would silently reduce over nothing and return each
        chip's local value.  Raise here instead of corrupting results.
        """
        if self.group > 1 and not self.axes:
            raise ValueError(
                f"topology ({self.n_nodes} nodes x {self.ppn} lanes) "
                "carries no mesh axis names, so collectives cannot "
                "execute on it; build it with Topology.from_mesh / "
                "Topology.from_axes (Topology.of is planning-only)"
            )
        return self

    # -- cached model-derived state ---------------------------------------

    def crossover_bytes(self) -> float:
        """Model-driven NAP↔MLA crossover for this grid (memoised).

        ``math.inf`` when NAP never loses in the model's search range
        (latency regime everywhere), ``0.0`` for degenerate lanes
        (``ppn == 1`` — NAP needs two lanes to trade steps for lanes).
        The large-message contender is the registry's *primary*
        (first-registered) bandwidth engine, not a hardcoded name.
        """
        return _crossover_bytes(
            self.n_nodes, self.ppn, self.params,
            _primary_bandwidth_engine(),
        )

    def optimal_pipeline_chunks(self, nbytes: float) -> int:
        """Model-optimal MLA pipeline depth for an ``nbytes`` payload."""
        return pm.optimal_pipeline_chunks(
            float(nbytes), self.n_nodes, self.ppn, self.params
        )

    def optimal_bucket_bytes(
        self,
        total_bytes: float,
        *,
        compute_seconds: float | None = None,
        max_buckets: int = 64,
    ) -> float:
        """Grad-sync fusion bucket target (overlap optimum, always finite)."""
        return pm.optimal_bucket_bytes(
            float(total_bytes), self.n_nodes, self.ppn, self.params,
            compute_seconds=compute_seconds, max_buckets=max_buckets,
        )

    def dispatched_cost(self, nbytes: float) -> float:
        """Modeled cost of one auto-dispatched allreduce of ``nbytes``."""
        return pm.dispatched_allreduce_cost(
            float(nbytes), self.n_nodes, self.ppn, self.params
        )

    # -- cached schedules / geometry --------------------------------------

    def schedule(self, engine: str, *, chunks: int = 1, elems: int | None = None):
        """The message schedule a registered engine would execute here."""
        return engine_schedule(
            engine, self.n_nodes, self.ppn, chunks=chunks, elems=elems
        )

    def chunk_splits(self, elems: int, chunks: int) -> tuple[int, ...]:
        """Ragged pipeline-chunk sizes (the exact executed splits)."""
        return napalg.ragged_splits(elems, max(1, chunks))

    def chunk_offsets(self, elems: int, chunks: int) -> tuple[int, ...]:
        return napalg.chunk_offsets(elems, max(1, chunks))

    def stripe_geometry(self, elems: int):
        """Ragged MLA stripe/block geometry ``(stripes, blocks)``."""
        return napalg.mla_stripe_geometry(self.n_nodes, self.ppn, elems)

    def internode_lower_bound(
        self, elems: int, collective: str = "allreduce"
    ) -> int:
        """Uneven-block lower bound on per-chip inter-node *elements*.

        The quantity the striped engines achieve exactly at the
        schedule/accounting layer: the full round trip for allreduce,
        the one-way halves for reduce_scatter / allgather.
        """
        if collective == "allreduce":
            return napalg.mla_internode_lower_bound(
                self.n_nodes, self.ppn, elems
            )
        if collective == "reduce_scatter":
            return napalg.rs_internode_lower_bound(
                self.n_nodes, self.ppn, elems
            )
        if collective == "allgather":
            return napalg.ag_internode_lower_bound(
                self.n_nodes, self.ppn, elems
            )
        raise ValueError(
            f"unknown collective {collective!r}; one of {COLLECTIVES}"
        )


def _primary_bandwidth_engine(collective: str = "allreduce") -> str:
    """The crossover's large-message contender: the first-registered
    bandwidth engine with a cost model — the same engine the tournament's
    registration-order tie-break prefers, so the regime split and the
    tournament agree on who anchors the bandwidth side."""
    for spec in _REGISTRY[collective].values():
        if spec.regime == "bandwidth" and spec.cost is not None:
            return spec.name
    raise ValueError(
        f"no bandwidth {collective} engine with a cost model is "
        "registered; cannot solve a latency/bandwidth crossover"
    )


@functools.lru_cache(maxsize=None)
def _crossover_bytes(
    n: int, ppn: int, params: pm.MachineParams, large: str
) -> float:
    if n <= 1:
        return math.inf  # no slow domain: NAP degenerates to psum
    if ppn <= 1:
        # NAP needs ppn >= 2 to trade steps for lanes; the striped path
        # degenerates to RS+AG over the slow domain, always valid here.
        return 0.0
    return pm.crossover_bytes(n, ppn, params, large=large)


# ---------------------------------------------------------------------------
# engine registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """One registered collective engine: capabilities + cost + lowering.

    ``execute`` is the shard_map-level lowering (signature per
    collective, see :class:`CommContext`); ``cost`` prices an ``s``-byte
    payload as ``cost(s, n, ppn, params)`` for the dispatch tournament
    and the crossover solver; ``build_schedule`` produces the message
    schedule the simulator replays.  ``regime`` structures the
    tournament: a ``latency`` engine wins below the memoised crossover,
    ``bandwidth`` engines fight a cost tournament above it, a
    ``fallback`` engine catches grids/ops nothing else supports, and
    ``baseline`` engines never auto-dispatch (explicit pin only).
    ``ops=None`` means op-independent (allgather moves bytes, no fold).
    ``ragged`` marks a ``build_schedule`` taking the payload element
    count for uneven-block accounting (``builder(n, ppn, elems)``);
    ``chunked`` marks a pipelined builder (``builder(n, ppn, chunks,
    elems)``) — :func:`engine_schedule` resolves the calling convention
    from these flags, so no caller keeps per-engine name tables.
    """

    name: str
    collective: str
    execute: Callable
    cost: Callable | None = None
    build_schedule: Callable | None = None
    ops: frozenset[str] | None = frozenset({"sum"})
    regime: str = "baseline"
    min_nodes: int = 1
    min_ppn: int = 1
    chunked: bool = False
    ragged: bool = False
    pipelined_variant: str | None = None
    legacy: Callable | None = None

    def supports(self, topology: Topology, op: str) -> bool:
        """Capability check: op + grid constraints."""
        if self.ops is not None and op not in self.ops:
            return False
        return (
            topology.n_nodes >= self.min_nodes
            and topology.ppn >= self.min_ppn
        )

    def describe(self) -> dict:
        """JSON-safe capability row (benchmark/CI listing)."""
        return {
            "name": self.name,
            "collective": self.collective,
            "regime": self.regime,
            "ops": sorted(self.ops) if self.ops is not None else "any",
            "min_nodes": self.min_nodes,
            "min_ppn": self.min_ppn,
            "chunked": self.chunked,
            "has_cost_model": self.cost is not None,
            "has_schedule": self.build_schedule is not None,
        }


_REGISTRY: dict[str, dict[str, EngineSpec]] = {c: {} for c in COLLECTIVES}


def register_engine(
    name: str,
    *,
    collective: str = "allreduce",
    ops: frozenset[str] | set[str] | None = frozenset({"sum"}),
    execute: Callable | None = None,
    cost: Callable | None = None,
    build_schedule: Callable | None = None,
    regime: str = "baseline",
    min_nodes: int = 1,
    min_ppn: int = 1,
    chunked: bool = False,
    ragged: bool = False,
    pipelined_variant: str | None = None,
    legacy: Callable | None = None,
    override: bool = False,
    verify: bool = True,
):
    """Register a collective engine (usable directly or as a decorator).

    A new engine — or a whole new backend — is one declaration::

        @register_engine(
            "mla_pipelined", ops={"sum", "max", "min"},
            cost=pm.cost_mla_pipelined_opt,
            build_schedule=napalg.build_mla_pipelined_schedule,
            regime="bandwidth", min_nodes=2, min_ppn=2, chunked=True,
        )
        def _execute(x, *, topology, op, pipeline_chunks):
            ...

    replacing the former edits across four files (``ALGORITHMS``,
    ``_MLA_OPS``, ``_LARGE_COSTS``, ``select_algorithm``).

    **Verify-on-register.**  When ``REPRO_VERIFY_ON_REGISTER`` is set in
    the environment (the test suite sets it), every registration with a
    schedule builder is statically verified by
    :mod:`repro.analysis.schedule_verifier` over a small grid matrix —
    match-completeness, deadlock-freedom, exactly-once reduction and
    byte accounting — before it becomes visible; a failing engine is
    rolled back out of the registry and the registration raises with the
    violation list.  ``verify=False`` opts a registration out of the
    *schedule* checks (for deliberately exotic schedules carrying their
    own proofs, and for native lowerings that have no schedule object).

    **Lint-on-register.**  Under the same environment flag every
    registration — ``verify=False`` included — is additionally traced
    to a jaxpr and run through :func:`lint_lowering`
    (:mod:`repro.analysis.spmd_lint`): collective uniformity, axis
    discipline, numerics flow and schedule-vs-jaxpr byte equality.
    There is no opt-out: an engine that cannot be traced and proven
    hang-free does not enter the tournament.
    """
    if collective not in _REGISTRY:
        raise ValueError(
            f"unknown collective {collective!r}; one of {COLLECTIVES}"
        )

    def _register(execute_fn: Callable) -> Callable:
        if name in _REGISTRY[collective] and not override:
            raise ValueError(
                f"{collective} engine {name!r} is already registered; "
                "pass override=True to replace it deliberately"
            )
        spec = EngineSpec(
            name=name,
            collective=collective,
            execute=execute_fn,
            cost=cost,
            build_schedule=build_schedule,
            ops=frozenset(ops) if ops is not None else None,
            regime=regime,
            min_nodes=min_nodes,
            min_ppn=min_ppn,
            chunked=chunked,
            ragged=ragged,
            pipelined_variant=pipelined_variant,
            legacy=legacy,
        )
        _REGISTRY[collective][name] = spec
        if _verify_on_register_enabled():
            try:
                if verify:
                    _verify_spec_quick(spec)
                # the jaxpr lint is NOT gated on ``verify``: engines
                # without a schedule to verify (the native lowerings)
                # still have an executed lowering to prove
                _lint_spec_quick(spec)
            except Exception:
                _REGISTRY[collective].pop(name, None)
                raise
        if legacy is not None and collective == "allreduce":
            _LEGACY_TABLE[name] = legacy
        return execute_fn

    if execute is not None:
        _register(execute)
        return _REGISTRY[collective][name]
    return _register


# registry-maintained backing store of the legacy ``ALGORITHMS`` view
_LEGACY_TABLE: dict[str, Callable] = {}
_LEGACY_VIEW = types.MappingProxyType(_LEGACY_TABLE)


def registered_engines(
    collective: str | None = None,
) -> dict[str, EngineSpec]:
    """The registry (one collective family, or all of them flattened)."""
    if collective is not None:
        if collective not in _REGISTRY:
            raise ValueError(
                f"unknown collective {collective!r}; one of {COLLECTIVES}"
            )
        return dict(_REGISTRY[collective])
    return {
        f"{c}:{n}": s for c, tab in _REGISTRY.items() for n, s in tab.items()
    }


def get_engine(name: str, collective: str = "allreduce") -> EngineSpec:
    """Resolve an engine by name, with a listing error on typos.

    This is the config/context build-time validation: a mistyped
    ``algorithm`` raises here — naming every registered engine — instead
    of surfacing as a bare ``KeyError`` deep inside tracing.
    """
    table = _REGISTRY[collective]
    spec = table.get(name)
    if spec is None:
        raise ValueError(
            f"unknown {collective} engine {name!r}; registered engines: "
            f"{sorted(table)} (or 'auto' for the model-driven dispatch)"
        )
    return spec


def _engine_collective(name: str) -> str:
    for coll, table in _REGISTRY.items():
        if name in table:
            return coll
    raise ValueError(
        f"unknown engine {name!r}; registered: "
        f"{sorted(registered_engines())}"
    )


def find_engine(name: str) -> EngineSpec:
    """Resolve an engine by name across all collective families."""
    return get_engine(name, _engine_collective(name))


def engine_schedule(
    name: str,
    n_nodes: int,
    ppn: int,
    *,
    chunks: int = 1,
    elems: int | None = None,
):
    """The message schedule a registered engine executes on an
    ``(n_nodes, ppn)`` grid — the single schedule-resolution point.

    The calling convention comes from the engine's declared flags
    (``chunked`` → ``builder(n, ppn, chunks, elems)``, ``ragged`` →
    ``builder(n, ppn, elems)``), so the simulator and Topology don't
    keep per-engine name tables that a new registration would miss.
    """
    spec = find_engine(name)
    if spec.build_schedule is None:
        raise ValueError(f"engine {spec.name!r} has no schedule builder")
    if spec.chunked:
        return spec.build_schedule(n_nodes, ppn, max(1, chunks), elems)
    if spec.ragged:
        return spec.build_schedule(n_nodes, ppn, elems)
    return spec.build_schedule(n_nodes, ppn)


def _verify_on_register_enabled() -> bool:
    return os.environ.get("REPRO_VERIFY_ON_REGISTER", "").lower() in (
        "1", "true", "yes",
    )


def _verify_spec_quick(spec: EngineSpec) -> None:
    """The verify-on-register gate: sweep the registration grids and
    raise (so the caller rolls the registry back) on any violation."""
    from ..analysis import schedule_verifier as _sv

    bad = []
    for n, ppn in _sv.REGISTER_GRIDS:
        for elems in (None, 19):
            r = _sv.verify_spec(
                spec, n, ppn, elems=elems, chunks=2 if spec.chunked else 1
            )
            if not r.ok:
                bad.append(r)
    if bad:
        lines = [
            f"  ({r.n_nodes}x{r.ppn}, elems={r.elems}) "
            f"[{v.rule}] {v.message}"
            for r in bad
            for v in r.violations
        ]
        raise ValueError(
            f"{spec.collective} engine {spec.name!r} failed static "
            "verification on registration:\n" + "\n".join(lines)
        )


def verify_engine(
    name: str,
    topology: Topology | None = None,
    *,
    n_nodes: int | None = None,
    ppn: int | None = None,
    elems: int | None = None,
    chunks: int = 1,
    grids=None,
    raise_on_violation: bool = True,
):
    """Statically verify a registered engine's schedules.

    The four passes of :mod:`repro.analysis.schedule_verifier` — match
    completeness, deadlock-freedom, exactly-once reduction correctness
    and byte-accounting equality against the engine's declared bound —
    run over one grid (a ``topology`` or ``n_nodes``/``ppn``) or a grid
    matrix (``grids``; defaults to the registration grids).  Returns the
    list of :class:`repro.analysis.VerificationReport` rows; raises
    ``ValueError`` listing every violation unless
    ``raise_on_violation=False``.

    New engines (ROADMAP open item 2) must pass this before entering
    the tournament — the test suite enforces it via verify-on-register.
    """
    from ..analysis import schedule_verifier as _sv

    spec = find_engine(name)
    if topology is not None:
        grid_list = [(topology.n_nodes, topology.ppn)]
    elif n_nodes is not None and ppn is not None:
        grid_list = [(n_nodes, ppn)]
    elif grids is not None:
        grid_list = list(grids)
    else:
        grid_list = list(_sv.REGISTER_GRIDS)

    reports = [
        _sv.verify_spec(
            spec, n, p, elems=elems,
            chunks=chunks if chunks > 1 else (2 if spec.chunked else 1),
        )
        for n, p in grid_list
    ]
    bad = [r for r in reports if not r.ok]
    if bad and raise_on_violation:
        lines = [
            f"  ({r.n_nodes}x{r.ppn}, elems={r.elems}) "
            f"[{v.rule}] {v.message}"
            for r in bad
            for v in r.violations
        ]
        raise ValueError(
            f"engine {name!r} failed static verification:\n"
            + "\n".join(lines)
        )
    return reports


#: grids the registration-time jaxpr lint sweeps (kept smaller than the
#: schedule verifier's REGISTER_GRIDS — tracing is costlier than graph
#: checks, and the jaxpr rules are grid-shape-generic)
_LINT_GRIDS = ((2, 2), (3, 2))


def lint_lowering(
    name: str,
    topology: Topology | None = None,
    *,
    n_nodes: int | None = None,
    ppn: int | None = None,
    elems: int | None = None,
    dtype="float32",
    op: str = "sum",
    chunks: int = 1,
    raise_on_violation: bool = True,
):
    """Statically lint a registered engine's *executed* lowering.

    Traces the engine's ``execute`` to a jaxpr under an abstract axis
    environment (no devices or mesh needed) and runs
    :func:`repro.analysis.spmd_lint.lint_jaxpr` over it: collective
    uniformity (the static hang detector), axis discipline, numerics
    flow, and byte accounting — the jaxpr-recomputed inter-node bytes
    per chip must equal the bound the engine's *schedule* declares,
    closing the schedule → jaxpr link of the three-layer proof chain
    (:mod:`repro.analysis`).

    The byte bound is resolved from the engine's declared flags: a
    non-ragged schedule builder gives the exact
    ``max_internode_bytes_per_chip`` at any payload; ragged/chunked
    engines are held to ``Topology.internode_lower_bound`` (exact when
    ``elems`` divides evenly, which the default payload does); native
    engines without a schedule are byte-audited report-only.

    Returns the :class:`repro.analysis.spmd_lint.SpmdLintReport`;
    raises ``ValueError`` listing every violation unless
    ``raise_on_violation=False``.  Like :func:`verify_engine` this is
    part of the registration gate — including for engines registered
    with ``verify=False``, which have no schedule to verify but still
    have a lowering to prove.
    """
    import jax
    import jax.numpy as jnp

    from ..analysis import spmd_lint as _sl

    spec = find_engine(name)
    if topology is not None:
        n, p = topology.n_nodes, topology.ppn
    elif n_nodes is not None and ppn is not None:
        n, p = int(n_nodes), int(ppn)
    else:
        n, p = _LINT_GRIDS[0]
    if n < spec.min_nodes or p < spec.min_ppn:
        raise ValueError(
            f"engine {name!r} needs at least "
            f"{spec.min_nodes}x{spec.min_ppn}, got {n}x{p}"
        )
    eff_chunks = chunks if chunks > 1 else (2 if spec.chunked else 1)
    # bind single mesh axis names; a caller topology with exactly one
    # axis per level keeps its names, anything else (unbound, or
    # multi-axis levels whose per-axis sizes a Topology doesn't carry)
    # falls back to synthetic names — the lint rules only care that the
    # axis *sizes* multiply out to the grid
    inter = ("pod",)
    intra = ("data",) if p > 1 else ()
    if topology is not None:
        if len(topology.inter_axes) == 1:
            inter = topology.inter_axes
        if len(topology.intra_axes) == 1 and p > 1:
            intra = topology.intra_axes
    topo = dataclasses.replace(
        topology if topology is not None else Topology.of(n, p),
        inter_axes=inter, intra_axes=intra,
    )
    dt = jnp.dtype(dtype)
    if elems is None:
        elems = n * p * eff_chunks * 4
    elems = int(elems)

    if spec.collective == "allgather":
        shard = -(-(-(-elems // p)) // n)  # ceil(ceil(e/ppn)/n)
        x = jax.ShapeDtypeStruct((shard,), dt)
        fn = functools.partial(spec.execute, topology=topo, elems=elems)
    else:
        x = jax.ShapeDtypeStruct((elems,), dt)
        if spec.collective == "reduce_scatter":
            fn = functools.partial(spec.execute, topology=topo, op=op)
        else:
            fn = functools.partial(
                spec.execute, topology=topo, op=op,
                pipeline_chunks=eff_chunks,
            )

    declared = None
    if spec.ragged or spec.chunked:
        if elems % (n * p * eff_chunks) == 0:
            declared = (
                topo.internode_lower_bound(elems, spec.collective)
                * dt.itemsize
            )
    elif spec.build_schedule is not None:
        declared = engine_schedule(
            name, n, p
        ).max_internode_bytes_per_chip(elems * dt.itemsize)

    axis_env = [(ax, n) for ax in inter] + [(ax, p) for ax in intra]
    closed = jax.make_jaxpr(fn, axis_env=axis_env)(x)
    report = _sl.lint_jaxpr(
        closed,
        axis_sizes=dict(axis_env),
        inter_axes=inter,
        intra_axes=intra,
        declared_internode_bytes=declared,
        label=f"{spec.collective}:{name}@{n}x{p}/{dt.name}",
    )
    if not report.ok and raise_on_violation:
        lines = [
            f"  [{v.rule}] {v.message}" for v in report.violations
        ]
        raise ValueError(
            f"engine {name!r} lowering failed the spmd lint on "
            f"{n}x{p} ({dt.name}):\n" + "\n".join(lines)
        )
    return report


def _lint_spec_quick(spec: EngineSpec) -> None:
    """The lint-on-register gate: trace and lint the engine's lowering
    over the lint grids, raising (so the caller rolls the registry
    back) on any violation.  Runs for *every* registration — the
    ``verify=False`` natives have no schedule but do have a lowering."""
    bad = []
    for n, p in _LINT_GRIDS:
        if n < spec.min_nodes or p < spec.min_ppn:
            continue
        r = lint_lowering(
            spec.name, n_nodes=n, ppn=p, raise_on_violation=False
        )
        if not r.ok:
            bad.append((n, p, r))
    if bad:
        lines = [
            f"  ({n}x{p}) [{v.rule}] {v.message}"
            for n, p, r in bad
            for v in r.violations
        ]
        raise ValueError(
            f"{spec.collective} engine {spec.name!r} lowering failed "
            "the spmd lint on registration:\n" + "\n".join(lines)
        )


class Decision(NamedTuple):
    """One dispatch decision: the engine and its pipeline depth."""

    engine: str
    chunks: int


def select_engine(
    topology: Topology,
    nbytes: int,
    op: str = "sum",
    *,
    collective: str = "allreduce",
    small_threshold_bytes: int | None = None,
    pipeline_chunks: int | None = None,
) -> Decision:
    """Capability-filtered cost tournament over the registered engines.

    1. **filter** — engines whose declared capabilities (ops, grid
       constraints) match this topology and op.  ``baseline`` engines
       never enter auto dispatch.
    2. **regime split** — when both a latency and a bandwidth engine are
       eligible, the switch point is ``small_threshold_bytes`` if given,
       else the memoised crossover of their declared cost models
       (:meth:`Topology.crossover_bytes`); at or below it the latency
       engine wins outright.
    3. **tournament** — above it the bandwidth engines compete on their
       declared ``cost`` at this payload size; earlier registration wins
       ties (so plain MLA beats pipelined MLA unless chunking strictly
       pays for its extra alpha steps — exactly
       :func:`perf_model.optimal_pipeline_chunks`' rule).
    4. **fallback** — grids/ops no latency or bandwidth engine supports
       (no slow domain; exotic ops) go to the fallback engine.

    ``pipeline_chunks`` pins the depth of a chunked winner (and promotes
    a plain bandwidth winner to its declared ``pipelined_variant`` when
    the pin exceeds 1).  Raises ``NotImplementedError`` listing every
    registered engine and its op set when nothing is eligible.
    """
    table = _REGISTRY[collective]
    eligible = [
        s
        for s in table.values()
        if s.regime in ("latency", "bandwidth", "fallback")
        and s.supports(topology, op)
    ]
    latency = [s for s in eligible if s.regime == "latency"]
    bandwidth = [s for s in eligible if s.regime == "bandwidth"]
    fallback = [s for s in eligible if s.regime == "fallback"]

    if not latency and not bandwidth:
        if not fallback:
            raise NotImplementedError(
                f"no registered {collective} engine supports op={op!r} on "
                f"grid (n={topology.n_nodes}, ppn={topology.ppn}); "
                f"registered engines: "
                + ", ".join(
                    f"{s.name}(ops="
                    f"{sorted(s.ops) if s.ops is not None else 'any'})"
                    for s in table.values()
                )
            )
        return Decision(fallback[0].name, 1)

    if latency and bandwidth:
        threshold = (
            float(small_threshold_bytes)
            if small_threshold_bytes is not None
            else topology.crossover_bytes()
        )
        if nbytes <= threshold:
            return Decision(latency[0].name, 1)
    if not bandwidth:
        return Decision(latency[0].name, 1)

    n, ppn, mp = topology.n_nodes, topology.ppn, topology.params
    best = bandwidth[0]
    best_cost = (
        best.cost(float(nbytes), n, ppn, mp) if best.cost else math.inf
    )
    for s in bandwidth[1:]:
        c = s.cost(float(nbytes), n, ppn, mp) if s.cost else math.inf
        if c < best_cost:
            best, best_cost = s, c

    if best.chunked:
        chunks = (
            max(1, int(pipeline_chunks))
            if pipeline_chunks is not None
            else topology.optimal_pipeline_chunks(nbytes)
        )
        return Decision(best.name, chunks)
    if pipeline_chunks is not None and best.pipelined_variant is not None:
        c = max(1, int(pipeline_chunks))
        return Decision(best.pipelined_variant if c > 1 else best.name, c)
    return Decision(best.name, 1)


# ---------------------------------------------------------------------------
# engine registrations
# ---------------------------------------------------------------------------

_ALL_OPS = frozenset(collectives._OPS)
_STRIPED_OPS = collectives._MLA_OPS


def _exec_psum(x, *, topology, op="sum", pipeline_chunks=None):
    return collectives._psum_allreduce(
        x, inter_axes=topology.inter_axes, intra_axes=topology.intra_axes,
        op=op,
    )


def _exec_nap(x, *, topology, op="sum", pipeline_chunks=None):
    return collectives.nap_allreduce(
        x, inter_axes=topology.inter_axes, intra_axes=topology.intra_axes,
        op=op,
    )


def _exec_rd(x, *, topology, op="sum", pipeline_chunks=None):
    return collectives.rd_allreduce(
        x, inter_axes=topology.inter_axes, intra_axes=topology.intra_axes,
        op=op,
    )


def _exec_smp(x, *, topology, op="sum", pipeline_chunks=None):
    return collectives.smp_allreduce(
        x, inter_axes=topology.inter_axes, intra_axes=topology.intra_axes,
        op=op,
    )


def _exec_mla(x, *, topology, op="sum", pipeline_chunks=None):
    return collectives.mla_allreduce(
        x, inter_axes=topology.inter_axes, intra_axes=topology.intra_axes,
        op=op, pipeline_chunks=pipeline_chunks or 1,
    )


def _exec_mla_pipelined(x, *, topology, op="sum", pipeline_chunks=None):
    return collectives.mla_pipelined_allreduce(
        x, inter_axes=topology.inter_axes, intra_axes=topology.intra_axes,
        op=op, pipeline_chunks=pipeline_chunks, params=topology.params,
    )


def _exec_ring(x, *, topology, op="sum", pipeline_chunks=None):
    return collectives.ring_allreduce(x, axes=topology.axes, op=op)


def _exec_rabenseifner(x, *, topology, op="sum", pipeline_chunks=None):
    # SMP-style large-message baseline: reduce inside the pod first so a
    # single de-duplicated payload crosses the slow domain, then RS+AG.
    v = x
    if topology.intra_axes:
        _, named_reduce, _ = collectives._OPS[op]
        v = named_reduce(v, topology.intra_axes)
    if not topology.inter_axes:
        return v
    return collectives.rabenseifner_allreduce(
        v, axes=topology.inter_axes, op=op
    )


def _cost_mla_pipelined_opt(s, n, ppn, p):
    return pm.cost_mla_pipelined(s, n, ppn, p, chunks=None)


register_engine(
    "nap", ops=_ALL_OPS, regime="latency", min_nodes=2, min_ppn=2,
    cost=pm.cost_nap, build_schedule=napalg.build_nap_schedule,
    execute=_exec_nap, legacy=collectives.nap_allreduce,
)
register_engine(
    "mla", ops=_STRIPED_OPS, regime="bandwidth", min_nodes=2,
    cost=pm.cost_mla, build_schedule=napalg.build_mla_schedule,
    ragged=True, execute=_exec_mla, legacy=collectives.mla_allreduce,
    pipelined_variant="mla_pipelined",
)
register_engine(
    "mla_pipelined", ops=_STRIPED_OPS, regime="bandwidth",
    min_nodes=2, min_ppn=2, cost=_cost_mla_pipelined_opt,
    build_schedule=napalg.build_mla_pipelined_schedule, chunked=True,
    execute=_exec_mla_pipelined, legacy=collectives.mla_pipelined_allreduce,
)
register_engine(
    "psum", ops=_ALL_OPS, regime="fallback", cost=pm.cost_psum,
    execute=_exec_psum, legacy=collectives._psum_allreduce,
)
register_engine(
    "rd", ops=_ALL_OPS, regime="baseline", cost=pm.cost_rd,
    build_schedule=napalg.build_rd_schedule, execute=_exec_rd,
    legacy=collectives.rd_allreduce,
)
register_engine(
    "smp", ops=_ALL_OPS, regime="baseline", cost=pm.cost_smp,
    build_schedule=napalg.build_smp_schedule, execute=_exec_smp,
    legacy=collectives.smp_allreduce,
)
register_engine(
    "ring", ops=_STRIPED_OPS, regime="baseline", execute=_exec_ring,
)
register_engine(
    "rabenseifner", ops=_STRIPED_OPS, regime="baseline",
    execute=_exec_rabenseifner,
)


def _exec_mla_rs(x, *, topology, op="sum"):
    return collectives.mla_reduce_scatter(
        x, inter_axes=topology.inter_axes, intra_axes=topology.intra_axes,
        op=op,
    )


def _exec_flat_rs(x, *, topology, op="sum"):
    return collectives.flat_reduce_scatter(
        x, axes=topology.axes, op=op,
        f32_accum=topology.n_nodes > 1,
    )


def _exec_mla_ag(x, *, topology, elems=None):
    return collectives.mla_allgather(
        x, inter_axes=topology.inter_axes, intra_axes=topology.intra_axes,
        elems=elems,
    )


def _exec_flat_ag(x, *, topology, elems=None):
    return collectives.flat_allgather(x, axes=topology.axes, elems=elems)


register_engine(
    "mla_rs", collective="reduce_scatter", ops=_STRIPED_OPS,
    regime="bandwidth", min_nodes=2, cost=pm.cost_reduce_scatter,
    build_schedule=napalg.build_mla_rs_schedule, ragged=True,
    execute=_exec_mla_rs,
)
register_engine(
    "psum_scatter", collective="reduce_scatter", ops=_STRIPED_OPS,
    regime="fallback", cost=pm.cost_reduce_scatter_flat,
    execute=_exec_flat_rs,
)
register_engine(
    "mla_ag", collective="allgather", ops=None, regime="bandwidth",
    min_nodes=2, cost=pm.cost_allgather,
    build_schedule=napalg.build_mla_ag_schedule, ragged=True,
    execute=_exec_mla_ag,
)
register_engine(
    "all_gather", collective="allgather", ops=None, regime="fallback",
    cost=pm.cost_allgather_flat, execute=_exec_flat_ag,
)


def legacy_execute_table():
    """The old ``collectives.ALGORITHMS`` view, derived from the registry:
    allreduce engines that still expose an axis-kwargs lowering.

    Read-only (a ``MappingProxyType`` over a registry-maintained dict):
    the old extension idiom ``ALGORITHMS["custom"] = fn`` would mutate a
    view the dispatcher never consults, so it now fails loudly — new
    engines register through :func:`register_engine` instead.
    """
    return _LEGACY_VIEW


# ---------------------------------------------------------------------------
# policy + context facade
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CommPolicy:
    """How a :class:`CommContext` dispatches and syncs.

    algorithm: allreduce engine name or ``"auto"`` (capability-filtered
      cost tournament; see :func:`select_engine`).  Validated here at
      build time against the registry — a typo raises immediately with
      the list of registered engines.
    mean: grad-sync only — divide by the group size (data-parallel
      averaging), with integer leaves rounded rather than silently left
      as sums.
    compress_bits: None (off) or 2..8 — quantised grad transport on the
      fused Pallas kernels (:mod:`repro.kernels.transport`) with
      per-leaf max-abs scales.  8 moves ``s8`` wire bytes (1/4 of the
      uncompressed f32 inter-node traffic); 4 packs two int4 nibbles
      per ``u8`` byte (1/8).  The node-aware shape (exact f32
      intra-node pre-combine, packed inter-node exchange) is documented
      in :mod:`repro.core.grad_sync`.
    error_feedback: carry per-chip EF residuals
      (:mod:`repro.optim.error_feedback`) so low-bit transport
      converges: each sync transports ``g + r`` and stores back what the
      wire quantizer dropped.  Requires ``compress_bits``; the caller
      threads the residual tree through
      :meth:`CommContext.sync_grads(ef_state=...) <CommContext.sync_grads>`.
    small_threshold_bytes: fixed latency/bandwidth switch override;
      ``None`` uses the memoised model crossover (possibly ``inf``).
    fuse_small_buckets: let the bucket planner fuse same-dtype float
      leaves (False = one bucket per leaf).
    bucket_bytes: fusion bucket target; ``None`` = overlap optimum from
      :meth:`Topology.optimal_bucket_bytes`.
    pipeline_chunks: MLA pipeline depth; ``None`` = model-optimal per
      payload.
    """

    algorithm: str = "auto"
    mean: bool = True
    compress_bits: int | None = None
    small_threshold_bytes: int | None = None
    fuse_small_buckets: bool = True
    bucket_bytes: int | None = None
    pipeline_chunks: int | None = None
    error_feedback: bool = False

    def __post_init__(self):
        if self.algorithm != "auto":
            get_engine(self.algorithm)  # raises with the engine listing
        if self.compress_bits is not None and not (
            2 <= int(self.compress_bits) <= 8
        ):
            raise ValueError(
                f"compress_bits must be None or 2..8, got "
                f"{self.compress_bits!r}"
            )
        if self.error_feedback and self.compress_bits is None:
            raise ValueError(
                "error_feedback=True requires compress_bits (residuals "
                "of an exact sync are identically zero)"
            )


@dataclasses.dataclass(frozen=True)
class CommContext:
    """Facade binding a :class:`Topology` to a dispatch policy.

    The collective methods execute inside a ``shard_map`` whose mesh
    carries the topology's axis names; dispatch decisions are host-side
    and static (payload sizes are trace constants), so the traced
    program contains exactly the schedule the model picked — the same
    decision the simulator replays and the planner prices.  Each call's
    ops carry the scope ``repro.comm.<engine>`` (with ``intra`` /
    ``inter`` phases beneath it), so a profile names them.
    """

    topology: Topology
    policy: CommPolicy = CommPolicy()

    # -- dispatch (host-side, static) -------------------------------------

    def dispatch(
        self,
        nbytes: int,
        op: str = "sum",
        *,
        collective: str = "allreduce",
        algorithm: str | None = None,
        pipeline_chunks: int | None = None,
    ) -> Decision:
        """The (engine, chunks) decision for an ``nbytes`` payload."""
        algo = algorithm if algorithm is not None else (
            self.policy.algorithm if collective == "allreduce" else "auto"
        )
        pin = (
            pipeline_chunks
            if pipeline_chunks is not None
            else self.policy.pipeline_chunks
        )
        if algo != "auto":
            spec = get_engine(algo, collective)
            if spec.chunked:
                chunks = (
                    max(1, int(pin))
                    if pin is not None
                    else self.topology.optimal_pipeline_chunks(nbytes)
                )
                return Decision(spec.name, chunks)
            if spec.pipelined_variant is not None and pin is not None:
                return Decision(spec.name, max(1, int(pin)))
            return Decision(spec.name, 1)
        return select_engine(
            self.topology,
            nbytes,
            op,
            collective=collective,
            small_threshold_bytes=self.policy.small_threshold_bytes,
            pipeline_chunks=pin,
        )

    def _engine_for(
        self, decision: Decision, op: str, collective: str
    ) -> EngineSpec:
        spec = get_engine(decision.engine, collective)
        if spec.ops is not None and op not in spec.ops:
            supporting = sorted(
                s.name
                for s in _REGISTRY[collective].values()
                if s.ops is None or op in s.ops
            )
            raise NotImplementedError(
                f"{collective} engine {spec.name!r} supports "
                f"{sorted(spec.ops)}, got op={op!r}; engines supporting "
                f"it: {supporting}"
            )
        return spec

    # -- collectives (inside shard_map) -----------------------------------

    def allreduce(
        self,
        x,
        op: str = "sum",
        *,
        algorithm: str | None = None,
        pipeline_chunks: int | None = None,
    ):
        """Allreduce over the topology's joint grid (model dispatched)."""
        self.topology.require_axes()
        nbytes = int(np.prod(x.shape)) * x.dtype.itemsize
        d = self.dispatch(
            nbytes, op, algorithm=algorithm, pipeline_chunks=pipeline_chunks
        )
        spec = self._engine_for(d, op, "allreduce")
        with scope(f"comm.{spec.name}"):
            return spec.execute(
                x, topology=self.topology, op=op, pipeline_chunks=d.chunks
            )

    def reduce_scatter(
        self, x, op: str = "sum", *, algorithm: str | None = None
    ):
        """Striped reduce-scatter: chip ``(node j, lane r)`` returns the
        fully reduced block ``(r, j)`` of the MLA stripe layout (padded
        to uniform per-chip shape ``ceil(ceil(s/ppn)/n)``).

        The ZeRO building block: each chip keeps only its optimizer
        shard's gradient slice; per-chip inter-node bytes are half the
        allreduce round trip (:func:`napalg.rs_internode_lower_bound` at
        the accounting layer).
        """
        self.topology.require_axes()
        nbytes = int(np.prod(x.shape)) * x.dtype.itemsize
        d = self.dispatch(
            nbytes, op, collective="reduce_scatter", algorithm=algorithm
        )
        spec = self._engine_for(d, op, "reduce_scatter")
        with scope(f"comm.{spec.name}"):
            return spec.execute(x, topology=self.topology, op=op)

    def allgather(
        self, x, *, elems: int | None = None, algorithm: str | None = None
    ):
        """Inverse of :meth:`reduce_scatter`: rebuild the full payload
        from per-chip blocks.  ``elems`` is the original payload size
        (needed to strip the uniform-shape padding; defaults to
        ``x.size * group``, i.e. no padding)."""
        self.topology.require_axes()
        total = int(elems if elems is not None else x.size * self.topology.group)
        nbytes = total * x.dtype.itemsize
        d = self.dispatch(
            nbytes, "sum", collective="allgather", algorithm=algorithm
        )
        spec = self._engine_for(d, "sum", "allgather")
        with scope(f"comm.{spec.name}"):
            return spec.execute(x, topology=self.topology, elems=total)

    # -- gradient sync (inside shard_map) ---------------------------------

    def sync_grads(self, grads, *, plan=None, ef_state=None):
        """Bucket-scheduled gradient allreduce of a pytree (the grad-sync
        executor under this context's policy; see
        :mod:`repro.core.grad_sync`).

        ``ef_state`` (optional, compressed transport only) is the
        per-chip error-feedback residual tree
        (:func:`repro.optim.error_feedback.ef_init`); when given, the
        call syncs ``grads + ef_state`` and returns ``(synced, new_ef)``.
        """
        from . import grad_sync

        return grad_sync.sync_with_context(
            grads, self, plan=plan, ef_state=ef_state
        )

    def sync_grads_sharded(self, grads):
        """ZeRO-style sharded sync: reduce-scatter each leaf, return the
        pytree of per-chip 1-D shards (see
        :func:`repro.core.grad_sync.sync_grads_sharded`)."""
        from . import grad_sync

        return grad_sync.sync_grads_sharded(grads, ctx=self)

    def plan(self, tree):
        """Host-side bucket plan for a gradient pytree under this
        context (:func:`repro.core.grad_sync.plan_for_tree`)."""
        from . import grad_sync

        return grad_sync.plan_for_tree(
            tree, cfg=self.policy, topology=self.topology
        )


# ---------------------------------------------------------------------------
# deprecation bookkeeping (shared by the shim entry points)
# ---------------------------------------------------------------------------

_DEPRECATION_WARNED: set[str] = set()


def warn_deprecated_once(key: str, replacement: str) -> None:
    """Emit one DeprecationWarning per shim per process (the shims stay
    silent after first use so hot loops don't spam)."""
    if key in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(key)
    warnings.warn(
        f"{key} is deprecated; use {replacement} "
        f"(repro.core.comm: Topology + CommContext)",
        DeprecationWarning,
        stacklevel=3,
    )
