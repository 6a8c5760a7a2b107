"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

Device planes are those named ``/device:TPU:<n>`` (or ``GPU``); their ``XLA
Ops`` line holds one event per operation run on the device, and each
operation belongs to the jitted program named by its ``hlo_module`` stat
(or, where that stat is absent, by the ``XLA Modules`` event containing
it).  The host plane's events named ``bench.*`` are the harness's own
``TraceAnnotation``s around each request.  All times are nanoseconds from
the start of the profile.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass
from pathlib import Path

__all__ = ["DeviceTrace", "Op", "load"]

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Op:
    device: int
    name: str
    program: str
    t0: int
    t1: int


def _merge(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class DeviceTrace:
    """Device operations, host annotations and the traced window."""

    def __init__(self, ops: list[Op], annotations: list[tuple[str, int, int]], window_ns: int,
                 n_devices: int):
        self.ops = ops
        self.annotations = annotations
        self.window_ns = window_ns
        self.n_devices = max(1, n_devices)

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def _busy(self, device: int) -> list[tuple[int, int]]:
        return _merge((o.t0, o.t1) for o in self.ops if o.device == device)

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the devices."""
        devices = sorted({o.device for o in self.ops})
        total = sum(b - a for d in devices for a, b in self._busy(d))
        return total / 1e9 / self.n_devices

    def program_seconds(self, pred) -> float:
        """Summed device time of every operation of the programs ``pred``
        selects by name."""
        return sum(o.t1 - o.t0 for o in self.ops if pred(o.program)) / 1e9

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations that took most device time, by
        ``program/op`` name, with their summed seconds."""
        acc: dict[str, int] = {}
        for o in self.ops:
            name = f"{o.program}/{o.name}" if o.program else o.name
            acc[name] = acc.get(name, 0) + o.t1 - o.t0
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest stretches of the window in which the first
        device ran nothing, each named by the harness requests in flight
        at its middle (``archive+retrieve``, ``wipe``, ``none``)."""
        devices = sorted({o.device for o in self.ops})
        busy = self._busy(devices[0]) if devices else []
        gaps, end = [], 0
        for a, b in busy:
            if a > end:
                gaps.append((end, a))
            end = max(end, b)
        if self.window_ns > end:
            gaps.append((end, self.window_ns))
        gaps.sort(key=lambda g: g[0] - g[1])
        starts = sorted((a, b, name) for name, a, b in self.annotations)
        keys = [s[0] for s in starts]
        out = []
        for a, b in gaps[:n]:
            mid = (a + b) // 2
            hi = bisect.bisect_right(keys, mid)
            names = sorted({name.removeprefix("bench.") for s, e, name in starts[:hi] if e >= mid})
            out.append(["+".join(names) or "none", (b - a) / 1e9])
        return out


def _stats(x) -> dict:
    return {k: v for k, v in x.stats}


def _program(name: str) -> str:
    """``jit_grib_pack(1406...)`` -> ``jit_grib_pack``: programs compiled
    for other static arguments share one name."""
    return name.split("(", 1)[0]


def _op_name(name: str) -> str:
    """The TPU trace names an operation by its HLO text
    (``%copy.1 = s32[...] copy(...)``); keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


#: device timestamps are rounded to whole nanoseconds
_SLACK_NS = 2


def _containing(modules: list[tuple[int, int, str]], t0: int, t1: int) -> str:
    """The program whose execution contains ``[t0, t1]``; executions may
    overlap, so look back past the latest one that started before."""
    i = bisect.bisect_right(modules, (t0 + _SLACK_NS, float("inf"), "")) - 1
    for j in range(i, max(i - 8, -1), -1):
        a, b, name = modules[j]
        if a <= t0 + _SLACK_NS and t1 <= b + _SLACK_NS:
            return name
    return ""


def load(path: str | Path) -> DeviceTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: list[Op] = []
    annotations: list[tuple[str, int, int]] = []
    window_ns = 0
    devices = 0
    for plane in pd.planes:
        name = plane.name
        if name == "Task Environment":
            st = _stats(plane)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window_ns = int(st["profile_stop_time"]) - int(st["profile_start_time"])
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        annotations.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
        elif _DEVICE_PLANE.match(name):
            dev = devices
            devices += 1
            lines = {line.name: line for line in plane.lines}
            modules = []
            if _MODULES_LINE in lines:
                modules = sorted((int(ev.start_ns), int(ev.end_ns), _program(ev.name))
                                 for ev in lines[_MODULES_LINE].events)
            if _OPS_LINE not in lines:
                continue
            for ev in lines[_OPS_LINE].events:
                t0, t1 = int(ev.start_ns), int(ev.end_ns)
                program = _program(str(_stats(ev).get("hlo_module", ""))) or _containing(modules, t0, t1)
                ops.append(Op(dev, _op_name(ev.name), program, t0, t1))
    if not window_ns:
        ends = [o.t1 for o in ops] + [b for _, _, b in annotations]
        window_ns = max(ends, default=0)
    return DeviceTrace(ops, annotations, window_ns, devices)
