"""The plain reference of GRIB simple packing, and the comparison with it.

Simple packing (WMO GRIB2 data representation template 5.0, binary and
decimal scale factors zero) stores ``code = round((x - min) / step)`` with
``step = (max - min) / (2**nbits - 1)``, and decodes ``code * step + min``.
The reference computes that in float64 with NumPy from the source field
alone; it imports nothing of the program and takes none of its outputs but
the decoded field under test.

The number compared is the widest gap between the decoded field and the
reference's decoded field, in quantisation steps of that field: float32
arithmetic in the program moves a code by at most a few, while a field
packed at another width, altered, or taken from another key is off by
hundreds or more.  :func:`control_decode` is the same reference in
bfloat16, the precision below the float32 that the configurations state.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

__all__ = ["control_decode", "readings", "reference_decode"]


def reference_decode(x: np.ndarray, nbits: int) -> tuple[np.ndarray, float]:
    """The float64 decode of ``x`` packed at ``nbits``, and its step."""
    x64 = np.asarray(x, np.float64)
    lo, hi = float(x64.min()), float(x64.max())
    maxcode = (1 << nbits) - 1
    step = max(hi - lo, 1e-30) / maxcode
    codes = np.clip(np.rint((x64 - lo) / step), 0, maxcode)
    return codes * step + lo, step


def control_decode(x: np.ndarray, nbits: int) -> np.ndarray:
    """The reference computed in bfloat16: the control, which the
    comparison has to fail."""
    bf = ml_dtypes.bfloat16
    xb = np.asarray(x, np.float32).astype(bf)
    lo, hi = xb.min(), xb.max()
    step = ((hi - lo) / bf((1 << nbits) - 1)).astype(bf)
    codes = np.clip(np.rint((xb - lo) / step), 0, (1 << nbits) - 1).astype(bf)
    return (codes * step + lo).astype(bf).astype(np.float64)


def readings(decoded: np.ndarray, source: np.ndarray, nbits: int) -> tuple[float, float]:
    """The two numbers compared for one field: the widest gap between
    ``decoded`` and the reference decode of ``source`` at ``nbits``, in
    quantisation steps, and the percentage of values farther than a
    quarter step from it.  Packed at ``nbits``, values sit on the
    reference's lattice but where float32 moved a code; packed at a wider
    width, half of them lie off it (the second number is only meaningful
    where a step is coarser than float32's resolution of the values: 16
    bits and below for these fields).  A decoded field of another shape,
    or holding non-finite values, reads an infinite gap and 100%."""
    ref, step = reference_decode(source, nbits)
    dec = np.asarray(decoded, np.float64)
    if dec.shape != ref.shape or not np.isfinite(dec).all():
        return float("inf"), 100.0
    g = np.abs(dec - ref) / step
    return float(g.max()), float((g > 0.25).mean() * 100.0)
