"""jit'd public wrapper: device-side GRIB simple packing for FDB archive."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import grib_pack_call, grib_unpack_call
from .ref import field_stats

__all__ = [
    "grib_pack",
    "grib_unpack",
    "pack_to_bytes",
    "payload_dtype",
    "unpack_from_bytes",
]


def payload_dtype(nbits: int) -> np.dtype:
    """The smallest unsigned container that holds an ``nbits`` code.

    GRIB's true bit-stream packs codes back to back; the wire container
    here is the next power-of-two integer width (uint8/uint16/uint32), so
    nbits in (8, 16, 32] trade no space while 24-bit codes ride in 4-byte
    containers — the effective-vs-wire telemetry reports container bytes.
    """
    if not isinstance(nbits, int) or not 1 <= nbits <= 32:
        raise ValueError(f"nbits must be an int in [1, 32], got {nbits!r}")
    if nbits <= 8:
        return np.dtype(np.uint8)
    if nbits <= 16:
        return np.dtype(np.uint16)
    return np.dtype(np.uint32)


@partial(jax.jit, static_argnames=("nbits", "interpret"))
def grib_pack(x: jax.Array, *, nbits: int = 16, interpret: bool | None = None):
    """x: (F, H, W) float -> (codes (F,H,W) int32, ref (F,), scale (F,))."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ref, scale, inv_scale = field_stats(x, nbits)
    codes = grib_pack_call(x, ref, inv_scale, nbits=nbits, interpret=interpret)
    return codes, ref, scale


@partial(jax.jit, static_argnames=("interpret",))
def grib_unpack(codes: jax.Array, ref: jax.Array, scale: jax.Array, *, interpret: bool | None = None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return grib_unpack_call(codes, ref, scale, interpret=interpret)


def pack_to_bytes(x: np.ndarray, nbits: int = 16) -> tuple[bytes, dict]:
    """Host-side convenience: one field (H, W) -> GRIB-ish byte payload."""
    dtype = payload_dtype(nbits)
    codes, ref, scale = grib_pack(jnp.asarray(x)[None], nbits=nbits)
    arr = np.asarray(codes[0]).astype(dtype)
    meta = {
        "ref": float(ref[0]),
        "scale": float(scale[0]),
        "shape": list(x.shape),
        "nbits": nbits,
        "dtype": dtype.name,
    }
    return arr.tobytes(), meta


def unpack_from_bytes(payload: bytes, meta: dict) -> np.ndarray:
    h, w = meta["shape"]
    dtype = (
        np.dtype(meta["dtype"])
        if "dtype" in meta
        else payload_dtype(meta.get("nbits", 16))
    )
    expected = h * w * dtype.itemsize
    if len(payload) != expected:
        raise ValueError(
            f"GRIB payload is {len(payload)} bytes but meta describes a "
            f"({h}, {w}) field of {dtype.name} codes ({expected} bytes) — "
            "payload and meta do not belong together"
        )
    codes = np.frombuffer(payload, dtype=dtype).reshape(h, w).astype(np.int32)
    out = grib_unpack(
        jnp.asarray(codes)[None],
        jnp.asarray([meta["ref"]], dtype=jnp.float32),
        jnp.asarray([meta["scale"]], dtype=jnp.float32),
    )
    return np.asarray(out[0])
