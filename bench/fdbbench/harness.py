"""One run of one cell: set-up, the measured window, the comparison.

``main`` is the body of ``bench/run.py``; ``measure`` and ``compare`` are
its parts, which ``bench/calibrate.py`` and the tests drive directly.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import inspect
import json
import shutil
import sys
import tempfile
import threading
import time
import traceback
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from .loop import Loop, Plan, tier_nbits
from .reference import control_decode, readings
from .roofline import load_peaks
from .spec import Cell, load_cell, load_reader

__all__ = ["NoDevice", "Outcome", "compare", "main", "measure", "require_tpu"]


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def _substitute(node, scratch: str):
    if isinstance(node, str):
        return node.replace("{scratch}", scratch)
    if isinstance(node, dict):
        return {k: _substitute(v, scratch) for k, v in node.items()}
    if isinstance(node, list):
        return [_substitute(v, scratch) for v in node]
    return node


#: spans the client's tracer holds: the whole traced run, server spans too
TRACE_CAPACITY = 1 << 21


def _server_ring() -> int:
    """Spans an in-process server's tracer holds: it builds a default
    ``Tracer``, and hands them over on each ``fetch_server_trace``."""
    from repro.obs import Tracer

    return inspect.signature(Tracer).parameters["capacity"].default


#: seconds between two fetches of the servers' spans in a traced run
FETCH_S = 2.0


def _wire_clients(fdb) -> list:
    """The wire clients of a tree: its nodes that fetch their server's
    spans, reached through the child attributes the program's
    ``install_tracer`` walks (``inner``, ``fdb``, ``tiers``, ``lanes``)."""
    found, stack, seen = [], [fdb], set()
    while stack:
        node = stack.pop()
        if node is None or id(node) in seen:
            continue
        seen.add(id(node))
        if hasattr(node, "fetch_server_trace"):
            found.append(node)
        stack += [getattr(node, a, None) for a in ("inner", "fdb")]
        stack += [*(getattr(node, "tiers", None) or ()), *(getattr(node, "lanes", None) or ())]
    return found


class _ServerSpans:
    """Fetches every wire client's server spans into the tree's tracer each
    :data:`FETCH_S` seconds while the window runs, so that no server's ring
    fills however many requests the window serves; ``most`` is the most
    spans one fetch brought."""

    def __init__(self, fdb):
        self.clients = _wire_clients(fdb)
        self.most = 0
        self.error: str | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True, name="bench-server-spans")

    def fetch(self) -> None:
        for c in self.clients:
            self.most = max(self.most, c.fetch_server_trace())

    def _run(self) -> None:
        while not self._stop.wait(FETCH_S):
            try:
                self.fetch()
            except Exception:  # noqa: BLE001 -- reported after the window
                self.error = traceback.format_exc()
                return

    def __enter__(self) -> "_ServerSpans":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()


def _rss_bytes() -> int:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


class _RssSampler:
    """Host resident memory once a second while the window runs."""

    def __init__(self):
        self.samples: list[int] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True, name="bench-rss")

    def _run(self) -> None:
        while True:
            self.samples.append(_rss_bytes())
            if self._stop.wait(1.0):
                return

    def __enter__(self) -> "_RssSampler":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        self.samples.append(_rss_bytes())


class _Lowerings:
    """Programs JAX has lowered in this process.  Its monitoring events are
    process-wide, so one listener, registered once, serves every run; a
    lowering inside the window means something traced or compiled there."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.count = 0
        self._mu = threading.Lock()

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            with self._mu:
                self.count += 1


_LOWERINGS: _Lowerings | None = None


def _lowerings() -> _Lowerings:
    global _LOWERINGS
    if _LOWERINGS is None:
        import jax

        _LOWERINGS = _Lowerings()
        jax.monitoring.register_event_duration_secs_listener(_LOWERINGS)
    return _LOWERINGS


def _rate(records) -> float | None:
    if not records:
        return None
    span = max(r.t1 for r in records) - min(r.t0 for r in records)
    return sum(r.nbytes for r in records) / span / 1e9 if span > 0 else None


def _p95_ms(records) -> float | None:
    if not records:
        return None
    return float(np.percentile([r.t1 - r.t0 for r in records], 95)) * 1e3


#: end-to-end metrics, taken by the harness itself on the host's clock
END_TO_END = {
    "archive_GBps": lambda o: _rate(o.loop.window_records("archive")),
    "retrieve_GBps": lambda o: _rate(o.loop.window_records("retrieve")),
    "retrieve_p95_ms": lambda o: _p95_ms(o.loop.window_records("retrieve")),
    "setup_s": lambda o: o.setup_s,
}


@dataclass
class Outcome:
    """What one run measured, before the comparison."""

    cell: Cell
    plan: Plan
    loop: Loop
    pool: np.ndarray
    setup_s: float
    prefill_s: float
    devices: list
    memory_peak_bytes: int
    #: host resident bytes, once a second through the window
    rss: list[int]
    #: programs lowered while the window ran (there should be none)
    window_lowerings: int
    spans: list | None = None
    device_trace: object | None = None
    peaks: dict | None = None


def measure(cell: Cell, seed: int, seconds: float, *, trace: bool, devices: list,
            t_start: float, tree: dict | None = None) -> Outcome:
    """Set up, run the window, read back; everything but the comparison.
    ``tree`` builds another tree than the configuration states, which the
    comparison still holds to the stated one (a planted fault)."""
    import jax

    from repro.compile_cache import use_compile_cache
    from repro.core import build_fdb

    from .fields import make_pool

    peaks = load_peaks(cell.root, devices[0].device_kind)
    use_compile_cache()
    plan = Plan(cell.config, cell.traffic)
    bases, scales = plan.pool_spread()
    pool = make_pool(seed, bases, scales, plan.grid)
    scratch = tempfile.mkdtemp(prefix="fdbbench-")
    profile_dir = None
    tree = dict(tree or cell.config["tree"])
    if trace:
        # the program's own option: one tracer on the whole tree; each wire
        # client hands it its server's spans when the tree closes
        tree["trace"] = {"capacity": TRACE_CAPACITY}
    try:
        fdb = build_fdb(_substitute(tree, scratch))
        try:
            loop = Loop(fdb, plan, pool, seed)
            t0 = time.perf_counter()
            loop.prefill()
            prefill_s = time.perf_counter() - t0
            loop.warm_reads()
            if trace:
                tracer = fdb.tracer

                @contextlib.contextmanager
                def annotate(name):
                    with jax.profiler.TraceAnnotation(name), tracer.span(name, parent=None):
                        yield

                loop.annotate = annotate
                profile_dir = tempfile.mkdtemp(prefix="fdbbench-profile-")
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(profile_dir, profiler_options=options)
            lowerings = _lowerings()
            setup_s = time.perf_counter() - t_start
            lowered = lowerings.count
            fetching = _ServerSpans(fdb) if trace else contextlib.nullcontext()
            with _RssSampler() as rss, fetching:
                t_open = time.perf_counter()
                loop.run(seconds)
                t_close = time.perf_counter()
            lowered = lowerings.count - lowered
            if trace:
                jax.profiler.stop_trace()
                loop.annotate = lambda name: contextlib.nullcontext()
            peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
            loop.readback()
            if trace:
                fetching.fetch()
        finally:
            fdb.close()
        spans = device_trace = None
        if trace:
            from . import xtrace

            if fetching.error:
                raise RuntimeError(f"fetching the servers' spans failed:\n{fetching.error}")
            if fetching.most >= _server_ring():
                raise RuntimeError(f"a server's trace ring ({_server_ring()} spans) filled up between "
                                   f"two fetches {FETCH_S} s apart: spans of the window may be lost, "
                                   "and the wire's self time would take in the server's")
            spans = [s.to_dict() for s in tracer.spans() if s.t0 >= t_open and s.t1 <= t_close]

            found = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
            if not found:
                raise RuntimeError("the profiler wrote no trace")
            device_trace = xtrace.load(found[0])
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
        if profile_dir:
            shutil.rmtree(profile_dir, ignore_errors=True)
    return Outcome(cell, plan, loop, pool, setup_s, prefill_s, devices, peak, rss.samples,
                   lowered, spans, device_trace, peaks)


def compare(o: Outcome, *, control: bool = False) -> dict[str, tuple[float, float]]:
    """Every number compared, with its limit: the widest gap in
    quantisation steps per codec width over the sampled fields, and the
    count of failed operations.  ``control`` puts the bfloat16 reference
    in the program's place, on the same fields."""
    limits = o.cell.config["limits"]
    widths = sorted({tier_nbits(o.plan.tree, o.plan.key(m, "", 0, *o.plan.step_fields[0]))
                     for m in o.plan.writers})
    gaps: dict[int, list[float]] = {n: [] for n in widths}
    offgrid: dict[int, list[float]] = {n: [] for n in widths}
    for slot, shift, nbits, decoded in o.loop.samples:
        src = o.plan.source(o.pool[slot:slot + 1], shift)[0]
        gap, off = readings(control_decode(src, nbits) if control else decoded, src, nbits)
        gaps[nbits].append(gap)
        offgrid[nbits].append(off)
    out = {}
    for n in widths:
        out[f"gap{n}"] = (max(gaps[n], default=float("inf")), float(limits[f"gap{n}"]))
        if f"offgrid{n}" in limits:
            out[f"offgrid{n}"] = (max(offgrid[n], default=100.0), float(limits[f"offgrid{n}"]))
    out["missing"] = (float(len(o.loop.failures)), float(limits["missing"]))
    return out


class Readings:
    """What a per-layer metric reader gets: the spans of the window, the
    reduced device trace, the peaks of the device and the window's
    requests."""

    def __init__(self, o: Outcome):
        self.spans = o.spans or []
        self.device = o.device_trace
        self.peaks = o.peaks
        self.loop = o.loop

    def requests(self, kind: str) -> list:
        return self.loop.window_records(kind)

    def effective_bytes(self, kind: str) -> int:
        return sum(r.nbytes for r in self.requests(kind))


def _device_info(o: Outcome) -> dict:
    import jax

    d = o.devices[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices()),
            "memory_peak_bytes": o.memory_peak_bytes}
    if o.device_trace is not None:
        info["busy_s"] = o.device_trace.busy_s()
        info["window_s"] = o.device_trace.window_s
    return info


def result_line(o: Outcome, checks: dict, trace: bool) -> dict:
    metrics = {}
    if trace:
        ctx = Readings(o)
        for m in o.cell.per_layer:
            value = load_reader(o.cell.root, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in o.cell.end_to_end:
            value = END_TO_END[m["name"]](o)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    window = [r for r in o.loop.records if r.kind in ("archive", "retrieve", "readback")]
    failed = sum(not r.ok for r in window)
    line = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": len(window),
        "failed": failed,
        "metrics": metrics,
        "device": _device_info(o),
    }
    if trace:
        line["breakdown"] = {"device_ops": o.device_trace.top_ops(10),
                             "idle_gaps": o.device_trace.idle_gaps(10)}
    line["checks"] = {name: {"value": v, "limit": lim} for name, (v, lim) in checks.items()}
    return line


def main(argv: list[str], *, root: Path, t_start: float, device_check=require_tpu) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py", description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(root, args.workload)
    try:
        devices = device_check(cell.chips)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    o = measure(cell, args.seed, args.seconds, trace=bool(args.trace), devices=devices,
                t_start=t_start)
    t0 = time.perf_counter()
    checks = compare(o)
    compare_s = time.perf_counter() - t0
    line = result_line(o, checks, bool(args.trace))
    err = sys.stderr
    counts = {k: len(o.loop.window_records(k)) for k in ("archive", "retrieve", "readback")}
    quarters = [o.rss[(len(o.rss) - 1) * q // 4] for q in range(5)]
    print(f"bench: prefill_s {o.prefill_s} setup_s {o.setup_s} requests {counts} "
          f"samples {len(o.loop.samples)} compare_s {compare_s} lowerings_in_window {o.window_lowerings} "
          f"host_rss_window_quarters {quarters} host_rss_window_max {max(o.rss)}", file=err)
    for what in o.loop.failures[:3]:
        print(f"bench: failure: {what}", file=err)
    print(json.dumps(line))
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} (limit {limit})", file=err)
    return 0
