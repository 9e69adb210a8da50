"""Compile the transport kernels and the compressed grad sync for a TPU
v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes off
the (8, 128) tiling, kernels that overflow fast memory, programs that
do not fit the chip.  These compiles catch that on the CPU.  Nothing
runs, so they say nothing about results or times.

The topology is described inside a module fixture (only the worker that
runs this file loads the TPU compiler library) and skips where it cannot
be described.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import comm
from repro.kernels import transport

# a real grad-sync bucket width
ELEMS = 1 << 22


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh22(topo):
    return Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), ("pod", "data"))


@pytest.mark.parametrize("rows", [1, 2, 4])
@pytest.mark.parametrize("bits", [8, 4])
def test_transport_kernels_compile_for_v5e(one_chip, bits, rows):
    """Both kernels at every row count the grad sync hands them (the
    wire-group size), with a ragged leaf boundary inside the bucket."""
    cols = ELEMS // rows
    offsets = (0, 12_345)
    kw = dict(offsets=offsets, bits=bits, base=7, row_stride=cols,
              interpret=False)
    x = jax.ShapeDtypeStruct((rows, cols), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one_chip)

    def quant(x, s):
        return transport.quantize_pack(x, s, **kw)

    def dequant(w, s):
        return transport.unpack_dequantize(w, s, cols=cols, **kw)

    wire = jax.eval_shape(quant, x, s)
    assert wire.dtype == transport.wire_dtype(bits)
    w = jax.ShapeDtypeStruct(wire.shape, wire.dtype, sharding=one_chip)
    for fn, args in ((quant, (x, s)), (dequant, (w, s))):
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bits", [8, 4])
def test_compressed_sync_grads_compiles_on_2x2(mesh22, monkeypatch, bits):
    """``CommContext.sync_grads`` with packed transport on the (2, 2)
    pod/data mesh: the kernels compiled (not interpreted) at R = 2."""
    # the backend here is the CPU; steer the kernels to their TPU path
    monkeypatch.setattr(transport, "_on_cpu", lambda: False)
    ctx = comm.CommContext(
        comm.Topology.from_mesh(mesh22),
        comm.CommPolicy(algorithm="nap", compress_bits=bits),
    )
    rep = NamedSharding(mesh22, P())
    grads = {
        "w": jax.ShapeDtypeStruct((2304, 1024), jnp.bfloat16, sharding=rep),
        "b": jax.ShapeDtypeStruct((2304,), jnp.float32, sharding=rep),
    }
    fn = jax.shard_map(
        ctx.sync_grads, mesh=mesh22, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    )
    hlo = jax.jit(fn).lower(grads).compile().as_text()
    # 4 kernels per bucket: quantize, unpack on receive, requantize,
    # unpack after the gather
    assert hlo.count("tpu_custom_call") >= 4


@pytest.mark.parametrize("bits", [8, 4])
def test_make_grad_sync_compiles_with_vma_checks(mesh22, monkeypatch, bits):
    """The library's grad-sync ``shard_map`` keeps ``check_vma`` where
    the kernels compile: the kernels' outputs carry their operands'
    ``vma``, so the packed path traces and compiles under the checks."""
    from repro.core import grad_sync

    monkeypatch.setattr(transport, "_on_cpu", lambda: False)
    assert transport.vma_checkable()
    spec = P(("pod", "data"))
    sync = grad_sync.make_grad_sync(
        comm.CommPolicy(algorithm="nap", compress_bits=bits), mesh22,
        data_axes=("pod", "data"), grad_specs={"w": spec, "b": spec},
    )
    stacked = NamedSharding(mesh22, spec)
    grads = {
        "w": jax.ShapeDtypeStruct((4, 2304, 1024), jnp.bfloat16,
                                  sharding=stacked),
        "b": jax.ShapeDtypeStruct((4, 2304), jnp.float32, sharding=stacked),
    }
    hlo = jax.jit(sync).lower(grads).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 4
