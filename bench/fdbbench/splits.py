"""Readers of the spans that split the codec's and the wire's host time.

Inside each ``codec.pack`` span the program records ``codec.pack.stack``,
``codec.pack.device`` and ``codec.pack.frame``; inside each
``codec.unpack`` span, ``codec.unpack.stack`` and ``codec.unpack.device``.
Inside each ``wire.*`` span of a wire client it records ``wire.send`` and
``wire.recv``, and a served op's ``server.*`` span carries ``queued_s``,
the time from its frame being read to a server thread starting it.  A
program without these finds nothing here, and every reader returns
``None``.
"""

from __future__ import annotations

from typing import Iterable

from .spans import duration

__all__ = ["children_of", "child_s_per_gb", "launch_ms", "queued_ms", "wire_io_s_per_gb"]


def children_of(spans: list[dict], parent, child: Iterable[str]) -> list[dict]:
    """The spans named in ``child`` whose parent's name ``parent`` selects."""
    names = {s["span_id"]: s["name"] for s in spans}
    child = set(child)
    return [s for s in spans if s["name"] in child and parent(names.get(s.get("parent_id"), ""))]


def child_s_per_gb(spans: list[dict], parent: str, child: Iterable[str]) -> float | None:
    """Summed duration of the ``child`` spans of the spans named ``parent``,
    per GB of the float32 bytes the parents carry (``effective_bytes``)."""
    kids = children_of(spans, lambda n: n == parent, child)
    nbytes = sum(s.get("attrs", {}).get("effective_bytes", 0) for s in spans if s["name"] == parent)
    if not kids or nbytes <= 0:
        return None
    return sum(duration(s) for s in kids) / (nbytes / 1e9)


def launch_ms(ctx, span: str, program: str) -> float | None:
    """Milliseconds a launch spends in its ``span`` (the host's view of the
    device call) beyond the device time of the jitted ``program``, averaged
    over the launches: dispatch, the copies to and from the device, and
    waits.  ``None`` where the device trace holds no operation of the
    program (a run without a device plane)."""
    found = [s for s in ctx.spans if s["name"] == span]
    if not found or ctx.device is None:
        return None
    if not any(o.program == program for o in ctx.device.ops):
        return None
    host = sum(duration(s) for s in found)
    return 1e3 * (host - ctx.device.program_seconds(lambda p: p == program)) / len(found)


def wire_io_s_per_gb(spans: list[dict], prefix: str, nbytes: int) -> float | None:
    """Summed ``wire.send`` and ``wire.recv`` time under the wire spans whose
    names start with ``prefix``, per GB of ``nbytes``."""
    io = children_of(spans, lambda n: n.startswith(prefix), ("wire.send", "wire.recv"))
    if not io or nbytes <= 0:
        return None
    return sum(duration(s) for s in io) / (nbytes / 1e9)


def queued_ms(spans: list[dict], traces: set[int]) -> float | None:
    """Mean ``queued_s`` of the ``server.*`` spans of ``traces``, in ms."""
    waits = [s["attrs"]["queued_s"] for s in spans
             if s["trace_id"] in traces and s["name"].startswith("server.")
             and "queued_s" in s.get("attrs", {})]
    return 1e3 * sum(waits) / len(waits) if waits else None
