"""Fused transport kernel validation + HLO regression.

Three layers, per the transport-kernel acceptance:

* interpret-mode Pallas vs the pure-jnp oracle — the wire bytes must be
  **bit-identical** (the oracle is the wire protocol; both ends of a
  link may run different impls);
* quantizer semantics — round-to-nearest/clip error bounds, packed int4
  width, per-leaf scale selection across static offsets;
* HLO regression (subprocess, multi-device) — the fused compressed
  bucket path stays exactly 4 ``pallas_call`` sites *per bucket*
  regardless of leaf count (no per-leaf launches, no stray
  convert/concat chain) and puts ``s8``/packed ``u8`` on the wire.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref, transport


def _payload(key, rows, cols, scale=1.0):
    return (jax.random.normal(key, (rows, cols)) * scale).astype(jnp.float32)


# ---------------------------------------------------------------------------
# pallas vs oracle: wire bytes bit-identical, roundtrip identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4, 5, 2])
@pytest.mark.parametrize(
    "rows,cols,base,row_stride",
    [
        (1, 256, 0, 0),
        (4, 256, 0, 256),      # sequential stripe blocks
        (4, 256, 1024, 0),     # a2a-received copies of one block
        (3, 100, 512, 128),    # ragged -> column padding path
        (2, 777, 33, 1024),
        # more blocks than one grid tile: a partial last tile per row
        (2, 256 * 300 + 17, 5, 256 * 301),
    ],
)
def test_wire_bytes_bit_identical(bits, rows, cols, base, row_stride):
    x = _payload(jax.random.PRNGKey(bits * 31 + rows), rows, cols)
    # two leaves splitting the global index space mid-window
    offsets = (0, base + cols // 2)
    scales = jnp.asarray([0.11, 0.37], jnp.float32)
    kw = dict(offsets=offsets, bits=bits, base=base, row_stride=row_stride)
    w_pl = transport.quantize_pack(x, scales, impl="pallas", **kw)
    w_ref = transport.quantize_pack(x, scales, impl="xla", **kw)
    assert w_pl.dtype == transport.wire_dtype(bits)
    np.testing.assert_array_equal(np.asarray(w_pl), np.asarray(w_ref))
    d_pl = transport.unpack_dequantize(
        w_pl, scales, cols=cols, impl="pallas", **kw
    )
    d_ref = transport.unpack_dequantize(
        w_pl, scales, cols=cols, impl="xla", **kw
    )
    assert d_pl.shape == (rows, cols)
    np.testing.assert_array_equal(np.asarray(d_pl), np.asarray(d_ref))


@settings(max_examples=10, deadline=None)
@given(
    bits=st.sampled_from([2, 3, 4, 6, 8]),
    rows=st.integers(min_value=1, max_value=5),
    cols=st.integers(min_value=1, max_value=700),
    base=st.integers(min_value=0, max_value=4096),
)
def test_wire_bytes_bit_identical_fuzz(bits, rows, cols, base):
    x = _payload(jax.random.PRNGKey(cols * 7 + rows), rows, cols, scale=3.0)
    offsets = (0,)
    scales = jnp.asarray([0.2], jnp.float32)
    kw = dict(offsets=offsets, bits=bits, base=base, row_stride=cols)
    w_pl = transport.quantize_pack(x, scales, impl="pallas", **kw)
    w_ref = transport.quantize_pack(x, scales, impl="xla", **kw)
    np.testing.assert_array_equal(np.asarray(w_pl), np.asarray(w_ref))
    d_pl = transport.unpack_dequantize(
        w_pl, scales, cols=cols, impl="pallas", **kw
    )
    d_ref = transport.unpack_dequantize(
        w_pl, scales, cols=cols, impl="xla", **kw
    )
    np.testing.assert_array_equal(np.asarray(d_pl), np.asarray(d_ref))


# ---------------------------------------------------------------------------
# quantizer semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_roundtrip_error_bound(bits, impl):
    x = _payload(jax.random.PRNGKey(0), 2, 600)
    qmax = float(2 ** (bits - 1) - 1)
    scale = float(jnp.max(jnp.abs(x))) / qmax
    scales = jnp.asarray([scale], jnp.float32)
    w = transport.quantize_pack(x, scales, offsets=(0,), bits=bits, impl=impl)
    d = transport.unpack_dequantize(
        w, scales, offsets=(0,), bits=bits, cols=600, impl=impl
    )
    # |x| <= qmax*scale by construction -> no clipping, only rounding
    assert float(jnp.max(jnp.abs(d - x))) <= scale / 2 + 1e-7


def test_int4_packs_two_elements_per_byte():
    x = _payload(jax.random.PRNGKey(1), 2, 512)
    scales = jnp.asarray([0.1], jnp.float32)
    w8 = transport.quantize_pack(x, scales, offsets=(0,), bits=8)
    w4 = transport.quantize_pack(x, scales, offsets=(0,), bits=4)
    assert w8.dtype == jnp.int8 and w8.shape == (2, 4, 128)
    assert w4.dtype == jnp.uint8 and w4.shape == (2, 2, 128)
    assert transport.wire_itemsize(4) == 0.5
    assert transport.wire_itemsize(8) == 1.0


def test_int4_split_half_nibble_layout():
    # block 8: byte k of a block = elem k (low nibble) | elem k+4 (high)
    vals = jnp.asarray([[1, 2, 3, -1, -2, 7, 0, -8.0]], jnp.float32)
    w = transport.quantize_pack(
        vals, jnp.ones((1,)), offsets=(0,), bits=4, block=8
    )
    got = np.asarray(w)[0, 0]
    q = np.asarray([1, 2, 3, -1, -2, 7, 0, -7])  # clip at qmax=7
    want = (q[:4] & 0xF) | ((q[4:] & 0xF) << 4)
    np.testing.assert_array_equal(got, want.astype(np.uint8))


def test_per_leaf_scale_selected_by_global_index():
    # two leaves: [0, 8) scale 1, [8, 16) scale 100; rows are stripe
    # blocks so row 1 covers the second leaf via base + row_stride
    x = jnp.full((2, 8), 60.0, jnp.float32)
    scales = jnp.asarray([1.0, 100.0], jnp.float32)
    d = transport.unpack_dequantize(
        transport.quantize_pack(
            x, scales, offsets=(0, 8), bits=8, base=0, row_stride=8, block=8
        ),
        scales, offsets=(0, 8), bits=8, cols=8, base=0, row_stride=8, block=8,
    )
    np.testing.assert_allclose(np.asarray(d[0]), 60.0)   # q=60, scale 1
    np.testing.assert_allclose(np.asarray(d[1]), 100.0)  # q=round(.6)=1


def test_rejects_bad_args():
    x = jnp.zeros((1, 8), jnp.float32)
    s = jnp.ones((1,))
    with pytest.raises(ValueError, match="bits"):
        transport.quantize_pack(x, s, offsets=(0,), bits=9)
    with pytest.raises(ValueError, match="offsets"):
        transport.quantize_pack(x, jnp.ones((2,)), offsets=(8, 0), bits=8)
    with pytest.raises(ValueError, match="wire block"):
        transport.unpack_dequantize(
            jnp.zeros((1, 100), jnp.int8), s, offsets=(0,), bits=8, cols=100
        )


# ---------------------------------------------------------------------------
# HLO regression: fused path, wire dtypes (multi-device subprocess)
# ---------------------------------------------------------------------------


def _run_subprocess(script: str) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, cwd=".", timeout=600,
    )
    assert proc.returncode == 0 and "OK" in proc.stdout, (
        proc.stdout[-2000:] + proc.stderr[-2000:]
    )


def test_fused_bucket_is_four_pallas_calls_and_wire_dtype():
    """One compressed bucket = exactly 4 ``pallas_call`` sites
    (quantize-stripe, unpack-receive, requantize, unpack-gather) no
    matter how many leaves it fuses — and EF adds none (its error
    decode rides the jnp oracle).  The compiled wire is ``s8`` at
    8 bits and packed ``u8`` at 4, with no wide-integer transport."""
    _run_subprocess(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        import sys; sys.path.insert(0, "src")
        from repro.core import comm, grad_sync
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("pod", "data"))

        def jaxpr_text(n_leaves, bits, ef):
            policy = comm.CommPolicy(
                algorithm="nap", mean=True, compress_bits=bits,
                error_feedback=ef,
            )
            shapes = [(64 + 32 * i,) for i in range(n_leaves)]

            def f(*leaves):
                topo = comm.Topology.from_mesh(mesh)
                ctx = comm.CommContext(topo, policy)
                grads = list(leaves[:n_leaves])
                ef_state = list(leaves[n_leaves:]) or None
                plan = grad_sync.plan_for_tree(
                    [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes],
                    cfg=policy, topology=topo,
                )
                out = grad_sync.sync_with_context(
                    grads, ctx, plan=plan, ef_state=ef_state
                )
                if ef_state is not None:
                    synced, new_ef = out
                    return (
                        jnp.concatenate(synced),
                        jnp.concatenate(new_ef),
                    )
                return jnp.concatenate(out)

            args = [jnp.zeros(s, jnp.float32) for s in shapes]
            if ef:
                args += [jnp.zeros(s, jnp.float32) for s in shapes]
            g = jax.shard_map(
                f, mesh=mesh,
                in_specs=tuple(P() for _ in args),
                out_specs=P() if not ef else (P(), P()),
                check_vma=False,
            )
            return str(jax.make_jaxpr(g)(*args)), g, args

        # the regression rules now live in repro.analysis.hlo_lint so
        # every compiled step (tests, the BENCH_7 driver, future
        # engines) checks the same invariants
        from repro.analysis import hlo_lint

        for n_leaves in (1, 3, 6):
            txt, _, _ = jaxpr_text(n_leaves, 8, False)
            hlo_lint.assert_clean(
                hlo_lint.lint_collective_counts(txt, {"pallas_call": 4}),
                f"leaves={n_leaves}",
            )
        # error feedback must not add pallas_call sites
        txt, _, _ = jaxpr_text(3, 4, True)
        hlo_lint.assert_clean(
            hlo_lint.lint_collective_counts(txt, {"pallas_call": 4}), "ef"
        )

        # compiled wire dtype: s8 at 8 bits, packed u8 at 4; the wire
        # collectives never move a wide-integer payload, and no
        # payload-sized (E = 288) f32 tensor crosses the inter-node
        # domain (ppn=4 exempts the intra-node f32 RS/AG phases)
        for bits in (8, 4):
            _, g, args = jaxpr_text(3, bits, False)
            hlo = jax.jit(g).lower(*args).compile().as_text()
            hlo_lint.assert_clean(
                hlo_lint.lint_compressed_wire(
                    hlo, bits=bits, payload_elems=288, ppn=4
                ),
                f"bits={bits}",
            )
        print("OK")
        """
    )
