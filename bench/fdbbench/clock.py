"""Put program spans on the device trace's clock.

Program spans (``repro.obs``) are timed by ``time.perf_counter``; the
profiler's trace (:mod:`.xtrace`) in nanoseconds from the start of the
profile, on a clock of its own that may run at a slightly different rate.
Every request of a traced window has twins, entered back to back on one
thread: its ``bench.*`` span and the ``TraceAnnotation`` of the same name.
A line fitted through the midpoints of the twins (an offset and a rate)
maps any span onto the trace's clock, the spans a server handed over
included, since an in-process server times its spans on the same clock.

The twins are paired without ids: the spans and annotations of one name,
each in the order of their midpoints, give a first line; every span is
then paired with the annotation of its name nearest to where that line
puts it, durations agreeing, and the line is fitted again with the pairs
it lies far from left out (a thread that lost the interpreter lock between
the two enters, a pair of near-simultaneous requests swapped).
"""

from __future__ import annotations

import bisect
from collections import Counter
from dataclasses import dataclass

import numpy as np

from .xtrace import DeviceTrace, _merge

__all__ = ["ClockFit", "fit", "fit_residuals", "gap_spans", "idle_gaps_ns", "to_profile_ns",
           "twins"]

#: twins' durations agree to within this (ns), or this share of the span's
DURATION_SLACK_NS = 200e3
DURATION_SLACK = 0.01
#: a pair further from the line than this many robust deviations is left out
REJECT_SIGMAS = 5.0
#: the least deviation (ns) the rejection assumes, so exact twins keep all
MIN_SIGMA_NS = 1e3


@dataclass(frozen=True)
class ClockFit:
    """Profile ns = ``offset_ns + ns_per_s * t`` for a span time ``t``."""

    offset_ns: float
    ns_per_s: float
    #: twins the line was fitted through, after rejection
    pairs: int

    def __call__(self, t):
        return self.offset_ns + self.ns_per_s * np.asarray(t, dtype=np.float64)


def _line(s: np.ndarray, a: np.ndarray, offset: float, slope: float
          ) -> tuple[float, float, np.ndarray]:
    """Least-squares line of ``a`` on ``s``, starting from the given one and
    refitted without the points it lies far from; returns offset, slope
    and the points kept."""
    keep = np.ones(len(s), dtype=bool)
    for _ in range(8):
        r = a - (offset + slope * s)
        centre = np.median(r[keep])
        sigma = max(1.4826 * float(np.median(np.abs(r[keep] - centre))), MIN_SIGMA_NS)
        new = np.abs(r - centre) <= REJECT_SIGMAS * sigma
        if new.sum() >= 2 and np.ptp(s[new]) > 0:
            mid = float(s[new].mean())
            fitted, c0 = np.polyfit(s[new] - mid, a[new], 1)
            slope, offset = float(fitted), float(c0) - float(fitted) * mid
        elif new.any():
            offset = float(np.median(a[new] - slope * s[new]))
        if not new.any() or (new == keep).all():
            break
        keep = new
    return offset, slope, keep


def _by_name(device: DeviceTrace) -> dict[str, list[tuple[float, int, int]]]:
    """Annotations by name, each ``(midpoint, start, end)`` in ns, sorted."""
    out: dict[str, list[tuple[float, int, int]]] = {}
    for name, a0, a1 in device.annotations:
        out.setdefault(name, []).append(((a0 + a1) / 2, a0, a1))
    for v in out.values():
        v.sort()
    return out


def _roots(spans: list[dict]) -> list[dict]:
    return [s for s in spans if s["name"].startswith("bench.") and s.get("parent_id") is None]


def _agree(ds_ns: float, da_ns: float) -> bool:
    return abs(ds_ns - da_ns) <= max(DURATION_SLACK_NS, DURATION_SLACK * ds_ns)


def _ranked(roots: list[dict], annots: dict) -> tuple[np.ndarray, np.ndarray]:
    """Each name's spans and annotations paired in the order of their
    midpoints, at the shift of rank (where one side has a few more) that
    leaves the pairs closest to a line."""
    s_all, a_all = [], []
    for name, v in annots.items():
        s = np.sort([(sp["t0"] + sp["t1"]) / 2 for sp in roots if sp["name"] == name])
        a = np.asarray([m for m, _, _ in v])
        best = None
        reach = abs(len(a) - len(s)) + 2
        for j in range(-reach, reach + 1):
            lo, hi = max(0, -j), min(len(s), len(a) - j)
            if hi - lo < 1:
                continue
            ss, aa = s[lo:hi], a[lo + j:hi + j]
            d = aa - 1e9 * ss
            if hi - lo >= 3 and np.ptp(ss) > 0:
                d = d - np.polyval(np.polyfit(ss - ss.mean(), d, 1), ss - ss.mean())
            score = (float(np.median(np.abs(d - np.median(d)))), -(hi - lo))
            if best is None or score < best[0]:
                best = (score, ss, aa)
        if best is not None:
            s_all.append(best[1])
            a_all.append(best[2])
    if not s_all:
        return np.empty(0), np.empty(0)
    return np.concatenate(s_all), np.concatenate(a_all)


def _pair(roots: list[dict], annots: dict, offset: float, slope: float):
    """Each root span with the annotation of its name whose midpoint lies
    nearest to where the line puts the span's, durations agreeing; each
    annotation is taken once, by the span nearest to it."""
    cands = []
    for k, sp in enumerate(roots):
        v = annots.get(sp["name"])
        if not v:
            continue
        mid = (sp["t0"] + sp["t1"]) / 2
        at = offset + slope * mid
        d = slope * (sp["t1"] - sp["t0"])
        i = bisect.bisect_left(v, (at, float("-inf"), 0))
        for j in range(max(0, i - 2), min(len(v), i + 2)):
            m, a0, a1 = v[j]
            if _agree(d, a1 - a0):
                cands.append((abs(m - at), k, sp["name"], j, mid, m))
    cands.sort()
    taken_s, taken_a, s, a = set(), set(), [], []
    for _, k, name, j, mid, m in cands:
        if k in taken_s or (name, j) in taken_a:
            continue
        taken_s.add(k)
        taken_a.add((name, j))
        s.append(mid)
        a.append(m)
    order = np.argsort(s)
    return np.asarray(s)[order], np.asarray(a)[order]


def twins(spans: list[dict], device: DeviceTrace) -> tuple[np.ndarray, np.ndarray, ClockFit]:
    """The paired midpoints of the ``bench.*`` twins (span seconds, profile
    ns) and the line fitted through them."""
    roots, annots = _roots(spans), _by_name(device)
    s, a = _ranked(roots, annots)
    if len(s) == 0:
        raise ValueError("no bench.* span has a TraceAnnotation twin to fit the clocks by")
    offset, slope, _ = _line(s, a, float(np.median(a - 1e9 * s)), 1e9)
    for _ in range(2):
        s, a = _pair(roots, annots, offset, slope)
        if len(s) == 0:
            raise ValueError("no bench.* span pairs with an annotation on the fitted line")
        offset, slope, keep = _line(s, a, offset, slope)
    return s, a, ClockFit(offset, slope, int(keep.sum()))


def fit(spans: list[dict], device: DeviceTrace) -> ClockFit:
    """The line from span seconds onto profile ns (see the module's text)."""
    return twins(spans, device)[2]


def fit_residuals(spans: list[dict], device: DeviceTrace) -> np.ndarray:
    """Profile ns of each paired annotation's midpoint less where the line
    puts its span's, over every pair, those the fit left out included."""
    s, a, clock = twins(spans, device)
    return a - clock(s)


def to_profile_ns(spans: list[dict], device: DeviceTrace, clock: ClockFit | None = None
                  ) -> list[dict]:
    """Copies of ``spans`` with ``t0_ns`` and ``t1_ns`` on the device trace's
    clock."""
    clock = clock or fit(spans, device)
    return [{**s, "t0_ns": float(clock(s["t0"])), "t1_ns": float(clock(s["t1"]))} for s in spans]


def idle_gaps_ns(device: DeviceTrace, n: int) -> list[tuple[int, int]]:
    """The ``n`` longest stretches ``(start, end)`` of the window in which
    the first device ran nothing, longest first."""
    devices = sorted({o.device for o in device.ops})
    busy = _merge((o.t0, o.t1) for o in device.ops if o.device == devices[0]) if devices else []
    gaps, end = [], 0
    for a, b in busy:
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if device.window_ns > end:
        gaps.append((end, device.window_ns))
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps[:n]


def gap_spans(device: DeviceTrace, spans: list[dict], n: int = 10,
              clock: ClockFit | None = None) -> list[dict]:
    """The ``n`` longest idle gaps of the device, each with the innermost
    program span open on each thread at the gap's middle, counted by name
    (``open``)."""
    clock = clock or fit(spans, device)
    t0 = clock(np.asarray([s["t0"] for s in spans], dtype=np.float64))
    t1 = clock(np.asarray([s["t1"] for s in spans], dtype=np.float64))
    out = []
    for a, b in idle_gaps_ns(device, n):
        mid = (a + b) / 2
        innermost: dict[tuple, int] = {}
        for i in np.flatnonzero((t0 <= mid) & (t1 >= mid)):
            s = spans[i]
            key = (s.get("proc"), s.get("thread"))
            j = innermost.get(key)
            if j is None or t0[i] > t0[j] or (t0[i] == t0[j] and t1[i] < t1[j]):
                innermost[key] = i
        names = Counter(spans[i]["name"] for i in innermost.values())
        out.append({"gap_s": (b - a) / 1e9, "start_s": a / 1e9,
                    "open": dict(sorted(names.items(), key=lambda kv: (-kv[1], kv[0])))})
    return out
