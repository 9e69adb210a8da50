"""Validate the trip-count-aware HLO analyzer against known-cost programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo


def _hlo(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


def test_plain_matmul_flops():
    def f(x, w):
        return x @ w

    txt = _hlo(f, jnp.ones((64, 128)), jnp.ones((128, 32)))
    st = analyze_hlo(txt)
    assert st.flops == pytest.approx(2 * 64 * 128 * 32, rel=0.01)
    assert st.dots == 1
    assert st.unknown_trip_whiles == 0


def test_scan_multiplies_by_trip_count():
    """The exact case cost_analysis() gets wrong by the trip count."""

    def f(x):
        def body(c, _):
            return c @ c, None

        out, _ = jax.lax.scan(body, x, None, length=17)
        return out

    txt = _hlo(f, jnp.ones((64, 64)))
    st = analyze_hlo(txt)
    expected = 17 * 2 * 64**3
    assert st.flops == pytest.approx(expected, rel=0.02)
    # cost_analysis undercounts by ~17x — that's why this module exists
    cost = jax.jit(f).lower(jnp.ones((64, 64))).compile().cost_analysis()
    assert cost["flops"] < expected / 8


def test_nested_scan_multipliers():
    def f(x):
        def inner(c, _):
            return c @ c, None

        def outer(c, _):
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None

        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    txt = _hlo(f, jnp.ones((32, 32)))
    st = analyze_hlo(txt)
    assert st.flops == pytest.approx(15 * 2 * 32**3, rel=0.05)


def test_einsum_batched_flops():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    txt = _hlo(f, jnp.ones((4, 16, 32)), jnp.ones((4, 32, 8)))
    st = analyze_hlo(txt)
    assert st.flops == pytest.approx(2 * 4 * 16 * 32 * 8, rel=0.01)


def test_memory_traffic_scales_with_trips():
    def f(x):
        def body(c, _):
            return c + 1.0, None

        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    n = 1 << 16
    txt = _hlo(f, jnp.ones((n,)))
    st = analyze_hlo(txt)
    # each iteration reads + writes the carry: >= 2 * 4B * n * 10
    assert st.memory_bytes >= 2 * 4 * n * 10
    assert st.memory_bytes < 50 * 4 * n * 10  # same order of magnitude


def test_collectives_inside_scan_multiply():
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        import sys; sys.path.insert(0, "src")
        from repro.launch.mesh import make_mesh
        from repro.launch.hlo_analysis import analyze_hlo

        mesh = make_mesh((8,), ("d",))
        def f(x):
            def body(c, _):
                return jax.lax.psum(c, "d"), None
            out, _ = jax.lax.scan(body, x, None, length=6)
            return out
        g = jax.shard_map(f, mesh=mesh, in_specs=P(None), out_specs=P(None))
        txt = jax.jit(g).lower(jnp.ones((1024,))).compile().as_text()
        st = analyze_hlo(txt)
        # 6 all-reduces of 4 KiB each, wire = 2*size*(7/8)
        expect = 6 * 2 * 4096 * 7 / 8
        assert abs(st.collectives["all-reduce"]["count"] - 6) < 1e-6, st
        assert abs(st.collective_bytes - expect) / expect < 0.05, st
        print("OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, cwd=".", timeout=300,
    )
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-2000:]
