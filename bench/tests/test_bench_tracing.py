"""The readers of the codec's and the wire's child spans, and the mapping
of program spans onto the device trace's clock, on synthetic spans and
traces whose answers are known."""

from __future__ import annotations

import numpy as np
import pytest

import benchtiny
from fdbbench import clock
from fdbbench.spec import load_reader
from fdbbench.xtrace import DeviceTrace, Op

ROOT = benchtiny.BENCH.parent

NEW = ("codec_host_s_per_GB.pack", "codec_host_s_per_GB.unpack", "codec_launch_ms.pack",
       "codec_launch_ms.unpack", "wire_io_s_per_GB.archive", "wire_io_s_per_GB.retrieve",
       "serve_queue_ms_per_request")
OLD = ("codec_pack_s_per_GB", "codec_unpack_s_per_GB", "wire_s_per_GB.archive",
       "wire_s_per_GB.retrieve", "catalogue_ms_per_request", "pack_hbm_roofline",
       "unpack_hbm_roofline")


class Spans:
    """A span list built by hand: ``add`` returns the new span's id."""

    def __init__(self):
        self.spans: list[dict] = []

    def add(self, name, t0, t1, parent=None, trace=0, proc="client", thread=1, **attrs):
        sid = len(self.spans) + 1
        s = {"name": name, "trace_id": trace, "span_id": sid, "t0": t0, "t1": t1,
             "thread": thread, "proc": proc}
        if parent is not None:
            s["parent_id"] = parent
        if attrs:
            s["attrs"] = attrs
        self.spans.append(s)
        return sid


class Ctx:
    """What a reader gets from the harness, for synthetic spans."""

    def __init__(self, spans, device, nbytes, requests):
        self.spans = spans
        self.device = device
        self.peaks = {"hbm_bytes_per_s": 1e11}
        self._nbytes = nbytes
        self._requests = requests

    def effective_bytes(self, kind):
        return self._nbytes[kind]

    def requests(self, kind):
        return [None] * self._requests[kind]


GB = 10**9
PACK = dict(nbits=16, fields=1, shape=[1000, 1000], effective_bytes=2 * GB, wire_bytes=GB)
UNPACK = dict(nbits=16, fields=1, shape=[1000, 1000], effective_bytes=4 * GB, wire_bytes=2 * GB)


def window(children: bool) -> list[dict]:
    """Two pack launches, one unpack launch, an archive, a flush and two
    retrieves over the wire; ``children`` adds what the program now
    records inside them."""
    sp = Spans()
    for k, t in enumerate((0.0, 10.0)):
        root = sp.add("bench.archive", t, t + 5.0, trace=10 + k)
        pack = sp.add("codec.pack", t, t + 1.0, root, trace=10 + k, **PACK)
        if children:
            sp.add("codec.pack.stack", t, t + 0.1, pack, trace=10 + k)
            sp.add("codec.pack.device", t + 0.1, t + 0.7, pack, trace=10 + k)
            sp.add("codec.pack.frame", t + 0.7, t + 0.95, pack, trace=10 + k)
        wire = sp.add("wire.archive_batch", t + 1.0, t + 4.0, root, trace=10 + k)
        if children:
            sp.add("wire.send", t + 1.0, t + 1.5, wire, trace=10 + k)
            sp.add("wire.recv", t + 3.5, t + 3.75, wire, trace=10 + k)
        attrs = {"queued_s": 0.5} if children else {}
        srv = sp.add("server.archive_batch", t + 2.0, t + 3.0, wire, trace=10 + k,
                     proc="server", thread=9, **attrs)
        sp.add("catalogue.archive_batch", t + 2.0, t + 2.5, srv, trace=10 + k, proc="server",
               thread=9)
    root = sp.add("bench.flush", 6.0, 7.0, trace=20)
    wire = sp.add("wire.flush", 6.0, 7.0, root, trace=20)
    if children:
        sp.add("wire.send", 6.0, 6.5, wire, trace=20)
        sp.add("wire.recv", 6.5, 7.0, wire, trace=20)
    for k, (t, queued) in enumerate(((20.0, 0.002), (30.0, 0.004))):
        root = sp.add("bench.retrieve", t, t + 2.0, trace=30 + k)
        wire = sp.add("wire.retrieve_many", t, t + 1.0, root, trace=30 + k)
        if children:
            sp.add("wire.send", t, t + 0.125, wire, trace=30 + k)
            sp.add("wire.recv", t + 0.75, t + 1.0, wire, trace=30 + k)
        attrs = {"queued_s": queued} if children else {}
        srv = sp.add("server.retrieve_many", t + 0.25, t + 0.5, wire, trace=30 + k,
                     proc="server", thread=9, **attrs)
        sp.add("catalogue.retrieve_batch", t + 0.25, t + 0.3, srv, trace=30 + k, proc="server",
               thread=9)
        if k == 0:
            unpack = sp.add("codec.unpack", t + 1.0, t + 2.0, root, trace=30 + k, **UNPACK)
            if children:
                sp.add("codec.unpack.stack", t + 1.0, t + 1.5, unpack, trace=30 + k)
                sp.add("codec.unpack.device", t + 1.5, t + 1.95, unpack, trace=30 + k)
    return sp.spans


DEVICE = DeviceTrace([Op(0, "grib_pack.1", "jit_grib_pack", 100, 100 + 200_000_000),
                      Op(0, "grib_unpack.1", "jit_grib_unpack", 10**9, 10**9 + 50_000_000)],
                     [], 40 * 10**9, 1)


def ctx(children: bool, device=DEVICE) -> Ctx:
    return Ctx(window(children), device, {"archive": 4 * GB, "retrieve": 2 * GB},
               {"archive": 2, "retrieve": 2})


def read(name: str, c: Ctx):
    return load_reader(ROOT, name)(c)


@pytest.mark.parametrize("name, expected", [
    # (0.1 + 0.25) s a launch, two launches, over 4 GB
    ("codec_host_s_per_GB.pack", 0.175),
    ("codec_host_s_per_GB.unpack", 0.125),
    # (2 x 0.6 s of device spans - 0.2 s on the device) / 2 launches
    ("codec_launch_ms.pack", 500.0),
    ("codec_launch_ms.unpack", 400.0),
    # (0.5 + 0.25) s an archive call, two calls, over 4 GB (the flush is not
    # counted); (0.125 + 0.25) s a retrieve, two, over 2 GB
    ("wire_io_s_per_GB.archive", 0.375),
    ("wire_io_s_per_GB.retrieve", 0.375),
    # the readers' server spans alone, not the archive's
    ("serve_queue_ms_per_request", 3.0),
])
def test_new_readers_on_known_spans(name, expected):
    assert read(name, ctx(True)) == pytest.approx(expected)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_a_program_without_the_spans(name):
    assert read(name, ctx(False)) is None


@pytest.mark.parametrize("name", OLD)
def test_accepted_readers_read_as_before(name):
    """The child spans and attributes move none of the accepted metrics."""
    assert read(name, ctx(True)) == read(name, ctx(False))


@pytest.mark.parametrize("kind", ["pack", "unpack"])
def test_launch_cost_needs_the_device_plane(kind):
    no_plane = DeviceTrace([], [], 40 * 10**9, 0)
    assert read(f"codec_launch_ms.{kind}", ctx(True, no_plane)) is None
    assert read(f"codec_launch_ms.{kind}", ctx(True, None)) is None


# ---------------------------------------------------------------------------
# the clock
# ---------------------------------------------------------------------------

OFFSET_NS = -1234.5e9
RATE = 1e9 * (1 + 300e-6)  # the profiler's clock runs 300 ppm fast
BASE_S = 1234.5 + 0.25  # perf_counter when the profile started


def twins(n=3000, seed=1, outliers=0.03, shift_ns=2e6, extra=5):
    """``n`` requests over 50 s on 16 threads, as bench spans and their
    annotations, the annotation entered 2 us before its span and left 3 us
    after; a share ``outliers`` of the annotations is moved by ``shift_ns``,
    and ``extra`` annotations have no span."""
    rng = np.random.default_rng(seed)
    starts = np.sort(rng.uniform(0, 50, n))
    dur = rng.uniform(1e-3, 0.2, n)
    names = rng.choice(["bench.archive", "bench.retrieve", "bench.flush"], n)
    spans, annots = [], []
    moved = rng.random(n) < outliers
    for k in range(n):
        t0, t1 = BASE_S + starts[k], BASE_S + starts[k] + dur[k]
        spans.append({"name": str(names[k]), "trace_id": k, "span_id": k + 1, "t0": t0, "t1": t1,
                      "thread": k % 16, "proc": "client"})
        a0 = OFFSET_NS + RATE * t0 - 2e3 + (shift_ns if moved[k] else 0)
        a1 = OFFSET_NS + RATE * t1 + 3e3 + (shift_ns if moved[k] else 0)
        annots.append((str(names[k]), int(a0), int(a1)))
    for k in range(extra):
        annots.append(("bench.retrieve", int(51e9 + k * 1e8), int(51.01e9 + k * 1e8)))
    return spans, annots, moved


def test_fit_recovers_offset_and_drift_past_outliers():
    spans, annots, moved = twins()
    device = DeviceTrace([], annots, 60 * 10**9, 1)
    fit = clock.fit(spans, device)
    # the twins' midpoints differ by a constant 0.5 us, which the offset takes
    assert fit.ns_per_s == pytest.approx(RATE, rel=1e-8)
    assert fit(BASE_S + 25.0) == pytest.approx(OFFSET_NS + RATE * (BASE_S + 25.0) + 500, abs=50)
    assert fit.pairs == len(spans) - moved.sum()
    r = np.abs(clock.fit_residuals(spans, device))
    assert len(r) == len(spans)
    assert np.median(r) < 100
    assert np.sum(r > 1e6) == moved.sum()  # each moved twin reads its 2 ms


def test_fit_pairs_twins_without_ids_when_durations_repeat():
    """Every request the same length: only the line can pair them."""
    spans, annots, _ = twins(n=500, outliers=0.0, extra=0)
    for s in spans:
        s["t1"] = s["t0"] + 0.01
    annots = [(name, a0, int(a0 + 0.01 * RATE + 5e3)) for name, a0, _ in annots]
    fit = clock.fit(spans, DeviceTrace([], annots, 60 * 10**9, 1))
    assert fit.ns_per_s == pytest.approx(RATE, rel=1e-6)
    assert fit.pairs == 500


def test_fit_without_twins_says_so():
    spans, _, _ = twins(n=10)
    with pytest.raises(ValueError, match="twin"):
        clock.fit(spans, DeviceTrace([], [], 10**9, 1))


def test_spans_map_onto_the_profile_clock():
    spans, annots, _ = twins(n=400, outliers=0.0)
    device = DeviceTrace([], annots, 60 * 10**9, 1)
    mapped = clock.to_profile_ns(spans, device)
    for s, (_, a0, a1) in zip(mapped, annots):
        assert s["t0_ns"] == pytest.approx(a0 + 2e3, abs=2e3)
        assert s["t1_ns"] == pytest.approx(a1 - 3e3, abs=2e3)
        assert "t0" in s and "name" in s


def test_gap_spans_name_what_each_thread_was_inside():
    fit = clock.ClockFit(offset_ns=-1e12, ns_per_s=1e9, pairs=2)
    ns = lambda t: (t - 1000.0) * 1e9  # noqa: E731 -- span seconds onto profile ns
    ops = [Op(0, "k", "jit_grib_pack", int(ns(1000.0)), int(ns(1001.0))),
           Op(0, "k", "jit_grib_pack", int(ns(1005.0)), int(ns(1005.5))),
           Op(0, "k", "jit_grib_pack", int(ns(1006.0)), int(ns(1010.0)))]
    device = DeviceTrace(ops, [], int(ns(1010.0)), 1)
    sp = Spans()
    # the longest gap runs 1001-1005 s; its middle is 1003 s
    root = sp.add("bench.archive", 1000.5, 1004.0, thread=1)
    sp.add("codec.pack", 1000.5, 1002.0, root, thread=1)
    wire = sp.add("wire.archive_batch", 1002.0, 1004.0, root, thread=1)
    sp.add("wire.send", 1002.0, 1002.5, wire, thread=1)
    sp.add("server.archive_batch", 1002.6, 1003.5, wire, proc="server", thread=7)
    for th in (2, 3):
        r = sp.add("bench.retrieve", 1002.9, 1003.2, thread=th)
        sp.add("wire.retrieve_many", 1002.9, 1003.1, r, thread=th)
    sp.add("bench.retrieve", 1004.0, 1004.5, thread=4)  # not open at the middle
    gaps = clock.gap_spans(device, sp.spans, 2, fit)
    assert [g["gap_s"] for g in gaps] == pytest.approx([4.0, 0.5])
    assert gaps[0]["start_s"] == pytest.approx(1.0)
    assert gaps[0]["open"] == {"wire.retrieve_many": 2, "server.archive_batch": 1,
                               "wire.archive_batch": 1}
    assert list(gaps[0]["open"]) == ["wire.retrieve_many", "server.archive_batch",
                                     "wire.archive_batch"]
    assert gaps[1]["open"] == {}


def test_idle_gaps_match_the_trace_reduction():
    """The gaps ``gap_spans`` names are the ones ``xtrace`` reports."""
    from pathlib import Path

    from fdbbench import xtrace

    trace = xtrace.load(Path(__file__).parent / "data" / "v5e_pack_unpack.xplane.pb")
    ours = [(b - a) / 1e9 for a, b in clock.idle_gaps_ns(trace, 10)]
    assert ours == pytest.approx([g for _, g in trace.idle_gaps(10)])


# ---------------------------------------------------------------------------
# the probe, end to end on the CPU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload", ["ens-0p1.wr", "hammer-1mib.wr"])
def test_probe_splits_a_traced_window(tmp_path, monkeypatch, workload):
    import contextlib
    import io
    import json

    import repro.compile_cache
    import trace_probe

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "")
    root = benchtiny.make_root(tmp_path)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = trace_probe.main(["--workload", workload, "--seeds", str(2**31 + 9), "--seconds", "1",
                               "--trace", "1"], root=root, device_check=benchtiny.cpu_devices)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["end_to_end"]["archive_GBps"] > 0
    got = line["per_layer"]
    # the CPU trace has no device plane: the launch costs find nothing to read
    assert got["codec_launch_ms.pack"] is None and got["codec_launch_ms.unpack"] is None
    for name in ("codec_host_s_per_GB.pack", "codec_host_s_per_GB.unpack",
                 "wire_io_s_per_GB.archive", "wire_io_s_per_GB.retrieve"):
        assert got[name] > 0, name
    assert got["serve_queue_ms_per_request"] >= 0
    for kind in ("pack", "unpack"):
        assert 0.5 < line["coverage"][kind]["share"] <= 1.0
    for kind in ("archive", "retrieve"):
        wire = line["wire"][kind]
        assert wire["spans"] > 0 and wire["send_s"] > 0 and wire["recv_s"] > 0
        assert wire["queued_s"] >= 0 and wire["wait_s"] > 0
    assert line["clock"]["twins"] > 0
    assert line["clock"]["median_abs_residual_us"] < 1000
    assert 1 <= len(line["gaps"]) <= 10
    assert all(g["open"] is not None for g in line["gaps"])
