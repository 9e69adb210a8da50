"""SPMD jaxpr lint coverage: real lowerings pass, mutants fail.

Mirrors ``test_schedule_verifier`` one layer down the proof chain:

* **sweep** — every registered engine's *executed lowering* lints clean
  via :func:`repro.core.comm.lint_lowering` (which also closes the
  byte-accounting loop against the schedule-declared bound);
* **mutation** — each rule family fires on a deliberately broken
  program (collective under a rank-varying predicate, asymmetric cond
  branches, sub-f32 cross-node accumulation, widened wire words,
  inflated byte bound, donated-buffer reuse): no vacuous passes, each
  paired with a clean twin;
* **property** — randomly generated *uniform* control-flow programs
  never produce a false positive;
* **integration** — the lint-on-register gate rejects a broken engine
  registered with ``verify=False`` and rolls the registry back.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from _hypothesis_compat import given, settings, st

from repro.analysis import spmd_lint
from repro.core import comm
from repro.kernels import transport

AXIS_ENV = [("pod", 2), ("data", 2)]
TOPO_KW = dict(
    axis_env=AXIS_ENV, inter_axes=("pod",), intra_axes=("data",)
)


def _lint(fn, *args, **kw):
    merged = {**TOPO_KW, **kw}
    return spmd_lint.lint_traced(fn, *args, **merged)


def _rules(report):
    return {v.rule for v in report.violations}


# ---------------------------------------------------------------------------
# sweep: every registered engine's lowering lints clean (bytes included)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("key", sorted(comm.registered_engines()))
def test_engine_lowering_lints_clean(key):
    _collective, name = key.split(":", 1)
    spec = comm.find_engine(name)
    n = max(2, spec.min_nodes)
    p = max(2, spec.min_ppn)
    report = comm.lint_lowering(
        name, n_nodes=n, ppn=p, raise_on_violation=True
    )
    assert report.ok
    assert report.collectives > 0


@pytest.mark.parametrize("name", ["nap", "mla", "rabenseifner", "psum"])
def test_engine_lowering_lints_clean_bf16(name):
    report = comm.lint_lowering(
        name, n_nodes=3, ppn=2, dtype="bfloat16", raise_on_violation=True
    )
    assert report.ok


def test_scheduled_engine_bytes_match_declared():
    """The byte-accounting loop actually closes: the report carries both
    the jaxpr-recomputed and the schedule-declared figures."""
    report = comm.lint_lowering("nap", n_nodes=3, ppn=2)
    assert report.declared_bytes is not None
    lo, hi = report.declared_bytes
    assert lo <= report.internode_bytes_per_chip <= hi


# ---------------------------------------------------------------------------
# mutation: collective-uniformity (the static hang detector)
# ---------------------------------------------------------------------------


def test_collective_under_rank_varying_cond_fires():
    def bad(x):
        pred = lax.axis_index("pod") == 0
        return lax.cond(
            pred,
            lambda v: lax.psum(v, ("pod", "data")),
            lambda v: lax.psum(v, ("pod", "data")) * 0.0,
            x,
        )

    report = _lint(bad, jnp.zeros((8,), jnp.float32))
    assert "collective-uniformity" in _rules(report)


def test_collective_under_rank_varying_while_fires():
    def bad(x):
        def cond(c):
            return lax.axis_index("pod") < 1

        def body(c):
            return lax.psum(c, "pod")

        return lax.while_loop(cond, body, x)

    report = _lint(bad, jnp.zeros((8,), jnp.float32))
    assert "collective-uniformity" in _rules(report)


def test_collective_under_uniform_cond_is_clean():
    def good(x):
        # pred derives from a whole-group reduction: provably uniform
        agreed = lax.psum(x, ("pod", "data"))
        pred = jnp.sum(agreed) > 0.0
        return lax.cond(
            pred,
            lambda v: lax.psum(v, "pod") + 1.0,
            lambda v: lax.psum(v, "pod") - 1.0,
            agreed,
        )

    report = _lint(good, jnp.zeros((8,), jnp.float32))
    assert report.ok, report.violations


# ---------------------------------------------------------------------------
# mutation: axis discipline
# ---------------------------------------------------------------------------


def test_asymmetric_cond_branches_fire():
    def bad(x):
        agreed = lax.psum(x, ("pod", "data"))  # pred itself is uniform
        pred = jnp.sum(agreed) > 0.0
        return lax.cond(
            pred,
            lambda v: lax.psum(v, "pod"),  # collective in one branch
            lambda v: v * 2.0,  # ... and not the other
            agreed,
        )

    report = _lint(bad, jnp.zeros((8,), jnp.float32))
    assert "axis-discipline" in _rules(report)


def test_unbound_axis_fires():
    """A collective over an axis the declared topology doesn't know —
    jax needs it in the trace env, the lint holds it against the
    *topology* under analysis."""

    def bad(x):
        return lax.psum(x, "model")

    closed = jax.make_jaxpr(
        bad, axis_env=AXIS_ENV + [("model", 2)]
    )(jnp.zeros((8,), jnp.float32))
    report = spmd_lint.lint_jaxpr(
        closed,
        axis_sizes=dict(AXIS_ENV),
        inter_axes=("pod",),
        intra_axes=("data",),
    )
    assert "axis-discipline" in _rules(report)


def test_shard_map_shadowing_fires():
    """A shard_map over axis names already bound by the trace-time axis
    env is shadowing; the same program linted as a mesh-level trace
    (``axes_bound_at_root=False``) is the legitimate first binding."""
    from jax.sharding import AbstractMesh

    # AbstractMesh traces on any device count — the lint only ever sees
    # the jaxpr, never a device
    mesh = AbstractMesh((2, 4), ("pod", "data"))
    inner = jax.shard_map(
        lambda v: lax.psum(v, ("pod", "data")),
        mesh=mesh,
        in_specs=jax.sharding.PartitionSpec("data"),
        out_specs=jax.sharding.PartitionSpec(),
        check_vma=False,
    )
    x = jnp.zeros((8,), jnp.float32)

    closed = jax.make_jaxpr(inner)(x)
    shadowed = spmd_lint.lint_jaxpr(
        closed,
        axis_sizes={"pod": 2, "data": 4},
        inter_axes=("pod",),
        intra_axes=("data",),
    )
    assert "axis-discipline" in _rules(shadowed)

    mesh_level = spmd_lint.lint_jaxpr(
        closed,
        axis_sizes={"pod": 2, "data": 4},
        inter_axes=("pod",),
        intra_axes=("data",),
        axes_bound_at_root=False,
    )
    assert mesh_level.ok, mesh_level.violations


# ---------------------------------------------------------------------------
# mutation: numerics flow
# ---------------------------------------------------------------------------


def test_bf16_psum_over_inter_fires():
    def bad(x):
        return lax.psum(x, "pod")

    report = _lint(bad, jnp.zeros((8,), jnp.bfloat16))
    assert "numerics-flow" in _rules(report)


def test_bf16_psum_upcast_is_clean():
    def good(x):
        return lax.psum(x.astype(jnp.float32), "pod").astype(jnp.bfloat16)

    report = _lint(good, jnp.zeros((8,), jnp.bfloat16))
    assert report.ok, report.violations


def test_bf16_fold_of_received_value_fires():
    def bad(x):
        recv = lax.ppermute(x, "pod", [(0, 1), (1, 0)])
        return x + recv  # bf16 accumulation of a cross-node value

    report = _lint(bad, jnp.zeros((8,), jnp.bfloat16))
    assert "numerics-flow" in _rules(report)


def test_f32_fold_of_received_value_is_clean():
    def good(x):
        recv = lax.ppermute(x, "pod", [(0, 1), (1, 0)])
        acc = x.astype(jnp.float32) + recv.astype(jnp.float32)
        return acc.astype(jnp.bfloat16)

    report = _lint(good, jnp.zeros((8,), jnp.bfloat16))
    assert report.ok, report.violations


def test_widened_wire_words_fire():
    """Packed wire words cast up to s32 before the collective: the wire
    moves 4x the declared width."""

    def bad(x):
        scales = jnp.max(jnp.abs(x)).reshape(1) / 127.0
        wire = transport.quantize_pack(
            x, scales, offsets=(0,), bits=8
        )
        wide = wire.astype(jnp.int32)
        return lax.ppermute(wide, "pod", [(0, 1), (1, 0)])

    report = _lint(bad, jnp.zeros((1, 256), jnp.float32))
    assert "numerics-flow" in _rules(report)


def test_packed_wire_words_are_clean():
    def good(x):
        scales = jnp.max(jnp.abs(x)).reshape(1) / 127.0
        wire = transport.quantize_pack(x, scales, offsets=(0,), bits=8)
        return lax.ppermute(wire, "pod", [(0, 1), (1, 0)])

    report = _lint(good, jnp.zeros((1, 256), jnp.float32))
    assert report.ok, report.violations


def test_undominated_scale_fires():
    def bad(x):
        scales = x[0, :1] + 1.0  # no max-abs ancestry
        return transport.quantize_pack(x, scales, offsets=(0,), bits=8)

    report = _lint(bad, jnp.zeros((1, 256), jnp.float32))
    assert "numerics-flow" in _rules(report)


# ---------------------------------------------------------------------------
# mutation: byte accounting
# ---------------------------------------------------------------------------


def _psum_pod(x):
    return lax.psum(x, "pod")


def test_byte_accounting_equality_holds():
    # psum of 8 f32 over 'pod' (2 nodes, 2 chips/node): every chip
    # exchanges 2 * (32 bytes / group of 2) with its 1 cross-node peer
    report = _lint(
        _psum_pod,
        jnp.zeros((8,), jnp.float32),
        declared_internode_bytes=32.0,
    )
    assert report.ok, report.violations
    assert report.internode_bytes_per_chip == 32.0


def test_inflated_declared_bound_fires():
    report = _lint(
        _psum_pod,
        jnp.zeros((8,), jnp.float32),
        declared_internode_bytes=1.0,
    )
    assert "byte-accounting" in _rules(report)


# ---------------------------------------------------------------------------
# mutation: alias-donation
# ---------------------------------------------------------------------------


def test_donated_buffer_reuse_fires():
    def bad(x):
        scales = jnp.max(jnp.abs(x)).reshape(1) / 127.0
        wire = transport.quantize_pack(
            x, scales, offsets=(0,), bits=8, donate_input=True
        )
        # the donated payload is read again after the call
        return jnp.sum(wire.astype(jnp.float32)) + jnp.sum(x)

    report = _lint(bad, jnp.zeros((1, 256), jnp.float32))
    assert "alias-donation" in _rules(report)


def test_donated_buffer_returned_fires():
    def bad(x):
        scales = jnp.max(jnp.abs(x)).reshape(1) / 127.0
        wire = transport.quantize_pack(
            x, scales, offsets=(0,), bits=8, donate_input=True
        )
        return wire, x  # donated payload escapes as an output

    report = _lint(bad, jnp.zeros((1, 256), jnp.float32))
    assert "alias-donation" in _rules(report)


def test_donation_of_dead_buffer_is_clean():
    def good(x):
        scales = jnp.max(jnp.abs(x)).reshape(1) / 127.0
        return transport.quantize_pack(
            x, scales, offsets=(0,), bits=8, donate_input=True
        )

    report = _lint(good, jnp.zeros((1, 256), jnp.float32))
    assert report.ok, report.violations


# ---------------------------------------------------------------------------
# property: uniform control flow never false-positives
# ---------------------------------------------------------------------------


@settings(max_examples=20)
@given(
    depth=st.integers(1, 3),
    coll=st.sampled_from(["psum", "pmax", "pmin"]),
    wrap=st.sampled_from(["plain", "cond", "scan"]),
    axes=st.sampled_from([("pod",), ("pod", "data")]),
)
def test_uniform_programs_lint_clean(depth, coll, wrap, axes):
    reduce_ = getattr(lax, coll)

    def step(v):
        return reduce_(v, axes)

    def prog(x):
        y = lax.psum(x, ("pod", "data"))  # uniformize once up front
        for _ in range(depth):
            if wrap == "cond":
                pred = jnp.sum(y) > 0.0
                y = lax.cond(
                    pred,
                    lambda v: step(v) + 1.0,
                    lambda v: step(v) - 1.0,
                    y,
                )
            elif wrap == "scan":
                y, _ = lax.scan(
                    lambda c, _x: (step(c), None), y, None, length=2
                )
            else:
                y = step(y)
        return y

    report = _lint(prog, jnp.zeros((8,), jnp.float32))
    assert report.ok, (depth, coll, wrap, axes, report.violations)


# ---------------------------------------------------------------------------
# integration: the lint-on-register gate
# ---------------------------------------------------------------------------


def test_register_gate_rejects_unlintable_engine():
    """An engine whose lowering hides a collective under a rank-varying
    predicate is rejected at registration even with ``verify=False``
    (it has no schedule to verify — but it has a lowering to prove),
    and the registry is rolled back."""
    name = "bad_spmd_lint_engine"

    def bad_execute(x, *, topology, op="sum", pipeline_chunks=1):
        pred = lax.axis_index(topology.inter_axes[0]) == 0
        return lax.cond(
            pred,
            lambda v: lax.psum(v, topology.axes),
            lambda v: lax.psum(v, topology.axes) * 0.0,
            x,
        )

    with pytest.raises(ValueError, match="collective-uniformity"):
        comm.register_engine(
            name, execute=bad_execute, verify=False, override=True
        )
    assert name not in comm.registered_engines("allreduce")
