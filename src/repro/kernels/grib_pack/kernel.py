"""Pallas TPU kernel for GRIB-style "simple packing" of weather fields.

The NWP I/O plane encodes every 2-D field before archiving (~25M fields /
70 TiB per operational run — paper §1.2); simple packing quantises floats to
``nbits`` integers with a per-field reference value and scale:

    packed = round((x - ref) / scale),   scale = (max-min) / (2^nbits - 1)

This is the bandwidth-bound device-side hotspot of the FDB write path, so it
runs as a tiled VMEM kernel (one row-block per grid cell, 8×128-aligned
tiles) producing int32 codes; the host packs the codes into the byte stream.
``unpack`` is the inverse.  Reductions (min/max) are a separate cheap XLA
pass in ops.py — fusing them would force a two-pass kernel for zero
bandwidth win.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grib_pack_call", "grib_unpack_call"]

#: bytes of one full-width row block of 4-byte elements.  The input and the
#: output block are each double-buffered, and the kernel's elementwise
#: temporaries take about as much again, so 2 MiB keeps a launch well
#: inside v5e's 16 MiB of scoped VMEM (256 rows of the 0.1-degree grid's
#: 3600 points, 3.7 MB a block, run out of it)
_BLOCK_BYTES = 2 << 20
_MAX_BLOCK_ROWS = 256


def _block_rows(h: int, w: int) -> int:
    """Rows per grid cell for an ``(H, W)`` field: a multiple of 8 sized by
    the lane-padded row width, or all of ``H`` when the field is shorter."""
    lanes = pl.cdiv(w, 128) * 128
    rows = max(8, _BLOCK_BYTES // (lanes * 4) // 8 * 8)
    return min(rows, _MAX_BLOCK_ROWS, h)


def _pack_kernel(x_ref, ref_ref, inv_scale_ref, out_ref, *, maxcode: int):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    code = jnp.round((x - ref_ref[i]) * inv_scale_ref[i])
    out_ref[...] = jnp.clip(code, 0.0, float(maxcode)).astype(jnp.int32)


def _unpack_kernel(c_ref, ref_ref, scale_ref, out_ref):
    i = pl.program_id(0)
    c = c_ref[...].astype(jnp.float32)
    out_ref[...] = c * scale_ref[i] + ref_ref[i]


def _field_call(kernel, x, ref, scale, out_dtype, interpret, name):
    """One launch over ``(F, H, W)``: grid ``(F, row blocks)``, the per-field
    scalars ``(F,)`` resident in SMEM (as 32-bit words) and indexed by the
    field axis."""
    f, h, w = x.shape
    rows = _block_rows(h, w)
    block = pl.BlockSpec((1, rows, w), lambda i, r: (i, r, 0))
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(f, pl.cdiv(h, rows)),
        in_specs=[block, scalar, scalar],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((f, h, w), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name=name,
    )(x, ref.astype(jnp.float32), scale.astype(jnp.float32))


def grib_pack_call(
    x: jax.Array,         # (F, H, W) fields
    ref: jax.Array,       # (F,) per-field reference (min)
    inv_scale: jax.Array, # (F,)
    *,
    nbits: int = 16,
    interpret: bool = False,
) -> jax.Array:
    kernel = functools.partial(_pack_kernel, maxcode=(1 << nbits) - 1)
    return _field_call(kernel, x, ref, inv_scale, jnp.int32, interpret, "grib_pack")


def grib_unpack_call(
    codes: jax.Array,  # (F, H, W) int32
    ref: jax.Array,    # (F,)
    scale: jax.Array,  # (F,)
    *,
    interpret: bool = False,
) -> jax.Array:
    return _field_call(_unpack_kernel, codes, ref, scale, jnp.float32, interpret, "grib_unpack")
