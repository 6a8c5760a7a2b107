"""Roofline arithmetic for the codec kernels, and the table of peaks.

The bytes are the least traffic each operation needs: packing reads every
float32 value once and writes its code at the container width; unpacking
reads the codes and writes float32.  Statistics passes, relayout copies
and int32 intermediates are not counted, so the share reads the same work
whatever implements it and cannot pass 100% unless the time leaves out
part of the work.
"""

from __future__ import annotations

import json
from pathlib import Path

__all__ = ["container_bytes", "kernel_share_pct", "load_peaks", "pack_bytes", "share_pct",
           "unpack_bytes"]


def container_bytes(nbits: int) -> int:
    """Bytes of the smallest unsigned container holding an ``nbits`` code."""
    if not 1 <= nbits <= 32:
        raise ValueError(f"nbits must lie in [1, 32], got {nbits}")
    return 1 if nbits <= 8 else 2 if nbits <= 16 else 4


def pack_bytes(n_values: int, nbits: int) -> int:
    return n_values * (4 + container_bytes(nbits))


def unpack_bytes(n_values: int, nbits: int) -> int:
    return n_values * (container_bytes(nbits) + 4)


def share_pct(nbytes: float, seconds: float, bytes_per_s: float) -> float | None:
    """Least time at the peak rate over the measured time, in percent."""
    if nbytes <= 0 or seconds <= 0:
        return None
    return 100.0 * nbytes / bytes_per_s / seconds


def kernel_share_pct(ctx, program: str, span: str, bytes_fn) -> float | None:
    """HBM roofline share of the jitted ``program`` over the traced window:
    ``bytes_fn(values, nbits)`` summed over the codec spans named ``span``
    (one a launch), at the peak HBM rate, over the summed device time of
    every operation of that program."""
    if ctx.device is None:
        return None
    seconds = ctx.device.program_seconds(lambda p: p == program)
    nbytes = 0
    for s in ctx.spans:
        if s["name"] == span:
            a = s["attrs"]
            h, w = a["shape"]
            nbytes += bytes_fn(a["fields"] * h * w, a["nbits"])
    return share_pct(nbytes, seconds, ctx.peaks["hbm_bytes_per_s"])


def load_peaks(root: Path, device_kind: str) -> dict:
    """The peaks of ``device_kind`` from ``bench/peaks.json``; a kind the
    table does not hold is an error, never a default."""
    with open(Path(root) / "bench" / "peaks.json") as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json "
                       f"(have {sorted(table['devices'])})") from None
