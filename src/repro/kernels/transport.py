"""Fused quantize-pack-stripe transport kernels (Pallas).

The compressed grad-sync path used to quantize, cast, concat and stripe
as separate XLA ops over the gradient before a single byte moved.  These
kernels collapse that chain into **one pass per transport hop**: each
row is viewed as ``(2*blocks, block/2)`` half-blocks and a grid cell
reads a run of consecutive blocks of one row of the fused f32 bucket
(dense in HBM and (8, 128)-tiled at any row count), looks up their
per-leaf scale from its *global flat index* (the leaf offsets of the
bucket — the same offsets :func:`repro.core.napalg.mla_stripe_geometry`
charges for stripe bytes — baked in as static index maps), rounds/clips
to the wire width and writes the wire bytes directly in stripe layout:

* ``bits == 8`` (or any width 2..8 except 4): one ``int8`` byte per
  element (``s8`` on the wire — 1/4 of f32);
* ``bits == 4``: two int4 nibbles packed per ``uint8`` byte with a
  split-half layout per block — wire byte ``k`` of a block carries
  element ``k`` in its low nibble and element ``k + block/2`` in its
  high nibble (``u8`` on the wire — 1/8 of f32).

:func:`unpack_dequantize` is the exact inverse on receive.  Both follow
the :mod:`repro.kernels.ops` convention: ``impl="pallas"`` compiles the
kernel (``interpret=True`` on CPU so tier-1 validates everywhere) and
``impl="xla"`` routes to the pure-jnp oracle in :mod:`repro.kernels.ref`,
which is bit-identical on the wire bytes.

Index plumbing: the input is (R, C) — R rows that are *blocks of a
stripe* (or per-rank copies of one block) — and the wire array is
(R, rows, block/2): row ``i``'s ``nb = ceil(C/block)`` blocks as two
half-block rows each (8-bit) or one packed row (int4; its low nibbles
are the block's first half, read with a stride-2 row slice).  With
``block == 256`` every wire row is 128 lanes, so flattening a wire
array is free on the TPU; small-row or 256-wide 8-bit arrays would
need relayouts that take XLA seconds per million elements to compile.
Element (i, c) of the padded
input corresponds to global flat-bucket index ``base + i*row_stride + c``
(``base`` is traced — it depends on ``lax.axis_index`` — and
``row_stride`` is static: the padded block length for sequential blocks,
0 for all-to-all-received per-rank copies of the same block).  Scales are
an (L,) traced vector (one per leaf, NAP-max agreed across the group);
leaf start offsets are static Python ints.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import ref

__all__ = [
    "quantize_pack",
    "unpack_dequantize",
    "vma_checkable",
    "wire_dtype",
    "wire_itemsize",
    "DEFAULT_BLOCK",
]

DEFAULT_BLOCK = 256
# blocks per grid cell: a multiple of 32 so 8-bit wire tiles are whole
TILE_BLOCKS = 256


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def wire_dtype(bits: int) -> jnp.dtype:
    """Dtype of the on-wire array: packed ``uint8`` for int4, ``int8``
    for every other supported width (2..8)."""
    return jnp.dtype(jnp.uint8) if bits == 4 else jnp.dtype(jnp.int8)


def wire_itemsize(bits: int) -> float:
    """Bytes per *element* on the wire (0.5 for packed int4, 1 else)."""
    return 0.5 if bits == 4 else 1.0


def _check_args(bits: int, block: int, scales_len: int, offsets) -> None:
    if not (2 <= bits <= 8):
        raise ValueError(f"transport bits must be in 2..8, got {bits}")
    if block % 2 or block < 2:
        raise ValueError(f"block must be even and >= 2, got {block}")
    if len(offsets) != scales_len:
        raise ValueError(
            f"{scales_len} scales but {len(offsets)} leaf offsets"
        )
    if list(offsets) != sorted(int(o) for o in offsets) or offsets[0] != 0:
        raise ValueError(f"offsets must be sorted and start at 0: {offsets}")


def _pad_cols(x: jax.Array, block: int) -> jax.Array:
    pad = (-x.shape[1]) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x


def _tile_index(base, start, *, rows: int, step: int, half: int,
                row_stride: int):
    """Global flat-bucket index of a (rows, half) tile of grid cell
    (i, j): tile row ``r`` lane ``c`` sits at ``start + r*step + c`` of
    input row ``i``."""
    shape = (rows, half)
    return (
        base
        + pl.program_id(0) * row_stride
        + start
        + lax.broadcasted_iota(jnp.int32, shape, 0) * step
        + lax.broadcasted_iota(jnp.int32, shape, 1)
    )


def _tile_scale(scales_ref, idx, *, offsets):
    """Per-element scale for a tile: leaf ``l`` spans global indices
    ``[offsets[l], offsets[l+1])`` (static loop — L is a trace-time
    constant, so this lowers to L-1 selects, not a gather)."""
    scale = jnp.full(idx.shape, scales_ref[0, 0], dtype=jnp.float32)
    for l in range(1, len(offsets)):
        scale = jnp.where(idx >= offsets[l], scales_ref[0, l], scale)
    return scale


def _halves(tile: int, block: int):
    """Grid cell j's ``tile`` blocks as (lo, hi) half-rows of the
    (2*nb, half) view: strided row slices and their index starts."""
    j = pl.program_id(1)
    return (
        (pl.ds(0, tile, stride=2), j * tile * block),
        (pl.ds(1, tile, stride=2), j * tile * block + block // 2),
    )


def _quant_kernel(
    base_ref, scales_ref, x_ref, o_ref, *, offsets, bits, block, row_stride
):
    half = block // 2
    qmax = float(2 ** (bits - 1) - 1)

    def quant(rows_sl, rows, start, step):
        idx = _tile_index(
            base_ref[0, 0], start, rows=rows, step=step, half=half,
            row_stride=row_stride,
        )
        scale = _tile_scale(scales_ref, idx, offsets=offsets)
        return jnp.clip(
            jnp.round(x_ref[rows_sl] / scale), -qmax, qmax
        ).astype(jnp.int32)

    if bits == 4:
        tile = o_ref.shape[0]
        (lo_sl, lo_start), (hi_sl, hi_start) = _halves(tile, block)
        lo = quant(lo_sl, tile, lo_start, block)
        hi = quant(hi_sl, tile, hi_start, block)
        o_ref[...] = ((lo & 0xF) | ((hi & 0xF) << 4)).astype(jnp.uint8)
    else:
        rows = x_ref.shape[0]
        q = quant(slice(None), rows, pl.program_id(1) * rows * half, half)
        o_ref[...] = q.astype(jnp.int8)


def _dequant_kernel(
    base_ref, scales_ref, w_ref, o_ref, *, offsets, bits, block, row_stride
):
    half = block // 2

    def scale(rows, start, step):
        idx = _tile_index(
            base_ref[0, 0], start, rows=rows, step=step, half=half,
            row_stride=row_stride,
        )
        return _tile_scale(scales_ref, idx, offsets=offsets)

    if bits == 4:
        tile = w_ref.shape[0]
        (lo_sl, lo_start), (hi_sl, hi_start) = _halves(tile, block)
        b = w_ref[...].astype(jnp.int32)
        lo = b & 0xF
        hi = (b >> 4) & 0xF
        lo = jnp.where(lo > 7, lo - 16, lo)
        hi = jnp.where(hi > 7, hi - 16, hi)
        o_ref[lo_sl] = lo.astype(jnp.float32) * scale(tile, lo_start, block)
        o_ref[hi_sl] = hi.astype(jnp.float32) * scale(tile, hi_start, block)
    else:
        rows = w_ref.shape[0]
        q = w_ref[...].astype(jnp.int32)
        o_ref[...] = q.astype(jnp.float32) * scale(
            rows, pl.program_id(1) * rows * half, half
        )


def _scalar_2d(v) -> jax.Array:
    return jnp.asarray(v, jnp.int32).reshape(1, 1)


def _blocked_call(kern, name, interpret, base, scales, x3, *, nb: int,
                  in_rows: int, out_rows: int, out_dtype):
    """Run ``kern`` over ``x3`` (R, nb*in_rows, half): one grid cell per
    row and run of ``TILE_BLOCKS`` blocks, each block ``in_rows`` rows
    in and ``out_rows`` rows out (the last run may be partial; its
    out-of-range rows are never written).  Returns
    (R, nb*out_rows, half) of ``out_dtype``."""
    R, _, half = x3.shape
    tile = min(nb, TILE_BLOCKS)
    L = scales.shape[0]
    base2, scales2 = _scalar_2d(base), scales.reshape(1, L)
    # the caller's vma, so the call traces under a check_vma shard_map
    vma = frozenset().union(*(jax.typeof(a).vma for a in (base2, scales2, x3)))
    return pl.pallas_call(
        kern,
        name=name,
        grid=(R, pl.cdiv(nb, tile)),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, L), lambda i, j: (0, 0)),
            pl.BlockSpec(
                (pl.Squeezed(), tile * in_rows, half), lambda i, j: (i, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (pl.Squeezed(), tile * out_rows, half), lambda i, j: (i, j, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (R, nb * out_rows, half), out_dtype, vma=vma
        ),
        interpret=_on_cpu() if interpret is None else interpret,
    )(base2, scales2, x3)


def vma_checkable() -> bool:
    """Whether a ``shard_map`` that runs these kernels can keep jax's
    ``check_vma``: yes where they compile; the CPU Pallas interpreter
    cannot lower under it."""
    return not _on_cpu()


def quantize_pack(
    x: jax.Array,
    scales: jax.Array,
    *,
    offsets,
    bits: int,
    base=0,
    row_stride: int = 0,
    impl: str = "pallas",
    block: int = DEFAULT_BLOCK,
    interpret: bool | None = None,
    donate_input: bool = False,
) -> jax.Array:
    """Quantize-and-pack ``x`` (R, C) f32 into wire bytes in one pass.

    Returns (R, rows, block/2) wire bytes, ``rows`` being ``2*nb`` at 8
    bits and ``nb`` packed at 4, ``nb = ceil(C/block)`` (columns
    zero-padded up to a ``block`` multiple; the pad quantizes to 0 and
    is sliced off by :func:`unpack_dequantize`).  ``scales`` is
    the (L,) per-leaf scale vector, ``offsets`` the static leaf start
    indices, ``base``/``row_stride`` the global-index plumbing (module
    docstring).

    ``donate_input=True`` declares that the caller is done with ``x``:
    its buffer may be reused for the wire output.  A true
    ``input_output_aliases`` is impossible here (the output dtype and
    width differ from the input), so the declaration is carried in the
    kernel *name* (``__donate<argnum>`` suffix) where the spmd lint's
    alias-donation rule statically proves the donated operand is never
    read again after the call.  Do **not** set it when the caller still
    needs ``x`` (e.g. the error-feedback path re-reads the stripe).
    """
    offsets = tuple(int(o) for o in offsets)
    scales = jnp.asarray(scales, jnp.float32).reshape(-1)
    _check_args(bits, block, scales.shape[0], offsets)
    xp = _pad_cols(jnp.asarray(x, jnp.float32), block)
    R, Cp = xp.shape
    nb, half = Cp // block, block // 2
    if impl == "xla":
        return ref.quantize_pack_ref(
            xp, scales, offsets=offsets, bits=bits, base=base,
            row_stride=row_stride, block=block,
        ).reshape(R, -1, half)
    kern = functools.partial(
        _quant_kernel,
        offsets=offsets, bits=bits, block=block, row_stride=int(row_stride),
    )
    name = f"quantize_pack_{bits}b" + ("__donate2" if donate_input else "")
    return _blocked_call(
        kern, name, interpret, base, scales, xp.reshape(R, 2 * nb, half),
        nb=nb, in_rows=2, out_rows=1 if bits == 4 else 2,
        out_dtype=wire_dtype(bits),
    )


def unpack_dequantize(
    wire: jax.Array,
    scales: jax.Array,
    *,
    offsets,
    bits: int,
    cols: int,
    base=0,
    row_stride: int = 0,
    impl: str = "pallas",
    block: int = DEFAULT_BLOCK,
    interpret: bool | None = None,
    donate_input: bool = False,
) -> jax.Array:
    """Inverse of :func:`quantize_pack`: wire bytes (R, rows, block/2)
    back to (R, cols) f32 values (``q * scale``), slicing off the block
    padding.

    ``base``/``row_stride``/``scales``/``offsets`` must describe the
    global indices of the *received* rows — for all-to-all-received
    per-rank copies of one block that is ``row_stride=0`` (every row
    dequantizes with the same index window).

    ``donate_input=True`` declares the received wire buffer dead after
    this call (see :func:`quantize_pack` — the declaration rides in the
    kernel name and is enforced by the spmd lint's alias-donation rule).
    """
    offsets = tuple(int(o) for o in offsets)
    scales = jnp.asarray(scales, jnp.float32).reshape(-1)
    _check_args(bits, block, scales.shape[0], offsets)
    half = block // 2
    per_block = 1 if bits == 4 else 2
    if wire.ndim != 3 or wire.shape[2] != half or wire.shape[1] % per_block:
        raise ValueError(
            f"wire shape {wire.shape} is not (R, {per_block}*blocks, "
            f"{half}): the wire block of bits={bits}, block={block}"
        )
    R, rows, _ = wire.shape
    nb = rows // per_block
    if impl == "xla":
        out = ref.unpack_dequantize_ref(
            wire.reshape(R, rows * half), scales, offsets=offsets, bits=bits,
            base=base, row_stride=row_stride, block=block,
        )
        return out[:, :cols]
    kern = functools.partial(
        _dequant_kernel,
        offsets=offsets, bits=bits, block=block, row_stride=int(row_stride),
    )
    name = (
        f"unpack_dequantize_{bits}b" + ("__donate2" if donate_input else "")
    )
    out = _blocked_call(
        kern, name, interpret, base, scales, wire, nb=nb, in_rows=per_block,
        out_rows=2, out_dtype=jnp.float32,
    )
    return out.reshape(R, nb * block)[:, :cols]
