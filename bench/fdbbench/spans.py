"""Reductions over finished spans (``Span.to_dict()`` records).

The spans come from ``repro.obs`` tracers installed on the tree and on each
in-process server; server spans are adopted into the client's tracer with
the client's trace ids.  The harness adds its own root spans around each
request (``bench.archive``, ``bench.retrieve``, ...), so every span of one
request shares that root's trace id.
"""

from __future__ import annotations

from typing import Callable, Iterable

__all__ = ["codec_s_per_gb", "duration", "named", "self_seconds", "traces_of", "union_seconds",
           "wire_s_per_gb"]

Pred = Callable[[str], bool]


def duration(s: dict) -> float:
    return s["t1"] - s["t0"]


def named(spans: Iterable[dict], pred: Pred) -> list[dict]:
    return [s for s in spans if pred(s["name"])]


def union_seconds(intervals: Iterable[tuple[float, float]]) -> float:
    """Length of the union of ``(t0, t1)`` intervals."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 <= end:
            continue
        total += t1 - max(t0, end)
        end = t1
    return total


def self_seconds(spans: list[dict], parent: Pred, child: Pred) -> float:
    """Summed duration of the spans ``parent`` selects, less the part of
    each that its direct children selected by ``child`` cover."""
    kids: dict[int, list[tuple[float, float]]] = {}
    for s in spans:
        if s.get("parent_id") is not None and child(s["name"]):
            kids.setdefault(s["parent_id"], []).append((s["t0"], s["t1"]))
    total = 0.0
    for s in named(spans, parent):
        inside = [(max(a, s["t0"]), min(b, s["t1"])) for a, b in kids.get(s["span_id"], ())]
        total += duration(s) - union_seconds((a, b) for a, b in inside if b > a)
    return total


def traces_of(spans: Iterable[dict], root: str) -> set[int]:
    """Trace ids of the spans named ``root``."""
    return {s["trace_id"] for s in spans if s["name"] == root}


def codec_s_per_gb(spans: list[dict], name: str) -> float | None:
    """Summed duration of the codec spans ``name`` per GB of the float32
    bytes they carry (their ``effective_bytes``)."""
    found = named(spans, lambda n: n == name)
    nbytes = sum(s.get("attrs", {}).get("effective_bytes", 0) for s in found)
    if not found or nbytes <= 0:
        return None
    return sum(duration(s) for s in found) / (nbytes / 1e9)


def wire_s_per_gb(spans: list[dict], prefix: str, nbytes: int) -> float | None:
    """Self time of the wire spans whose names start with ``prefix``, less
    their ``server.*`` children (the served tree's own work), per GB of
    ``nbytes``."""
    if nbytes <= 0 or not any(s["name"].startswith(prefix) for s in spans):
        return None
    wire = self_seconds(spans, lambda n: n.startswith(prefix), lambda n: n.startswith("server."))
    return wire / (nbytes / 1e9)
