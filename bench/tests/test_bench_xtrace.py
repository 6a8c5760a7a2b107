"""The trace reduction and the roofline arithmetic, on a recorded trace.

``data/v5e_pack_unpack.xplane.pb`` was recorded on one TPU v5e chip: one
``grib_pack`` (16 bits) and one ``grib_unpack`` launch on a (2, 64, 256)
float32 batch, inside ``bench.archive`` and ``bench.retrieve``
annotations.
"""

from __future__ import annotations

from pathlib import Path

import pytest

import benchtiny  # noqa: F401  (puts the harness on the path)
from fdbbench import roofline, spans, xtrace

TRACE = Path(__file__).parent / "data" / "v5e_pack_unpack.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return xtrace.load(TRACE)


def test_device_ops_belong_to_their_programs(trace):
    assert trace.n_devices == 1
    programs = {o.program for o in trace.ops}
    assert programs == {"jit_grib_pack", "jit_grib_unpack"}
    names = {(o.program, o.name) for o in trace.ops}
    assert ("jit_grib_pack", "grib_pack.1") in names
    assert ("jit_grib_pack", "fusion") in names  # the statistics pass
    assert ("jit_grib_unpack", "grib_unpack.1") in names


def test_program_time_is_the_sum_of_its_ops(trace):
    pack = trace.program_seconds(lambda p: p == "jit_grib_pack")
    unpack = trace.program_seconds(lambda p: p == "jit_grib_unpack")
    assert pack == pytest.approx(1.57e-06)
    assert unpack == pytest.approx(1.196e-06)
    # ops of one launch do not overlap: busy time is their sum
    assert trace.busy_s() == pytest.approx(pack + unpack)
    assert trace.busy_s() < trace.window_s


def test_breakdown_names_ops_and_gaps(trace):
    top = trace.top_ops(3)
    assert top[0] == ["jit_grib_unpack/grib_unpack.1", pytest.approx(8.9e-07)]
    assert len(top) == 3
    gaps = trace.idle_gaps(10)
    assert len(gaps) <= 10
    assert sum(g for _, g in gaps) <= trace.window_s - trace.busy_s() + 1e-9
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert {a for a, _, _ in trace.annotations} == {"bench.archive", "bench.retrieve"}


def test_roofline_bytes_count_the_least_traffic():
    assert roofline.container_bytes(8) == 1
    assert roofline.container_bytes(16) == 2
    assert roofline.container_bytes(24) == 4
    n = 2 * 64 * 256
    assert roofline.pack_bytes(n, 16) == n * 6
    assert roofline.unpack_bytes(n, 24) == n * 8
    with pytest.raises(ValueError):
        roofline.container_bytes(33)


def test_roofline_share_on_the_recorded_launches(trace):
    peaks = roofline.load_peaks(benchtiny.BENCH.parent, "TPU v5 lite")
    n = 2 * 64 * 256
    pack = roofline.share_pct(roofline.pack_bytes(n, 16),
                              trace.program_seconds(lambda p: p == "jit_grib_pack"),
                              peaks["hbm_bytes_per_s"])
    # 196 KiB at 819 GB/s is 0.24 us of a 1.57 us program
    assert pack == pytest.approx(100 * n * 6 / 819e9 / 1.57e-06)
    assert 0 < pack < 100
    assert roofline.share_pct(0, 1.0, 819e9) is None
    assert roofline.share_pct(1, 0.0, 819e9) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.load_peaks(benchtiny.BENCH.parent, "TPU v9 imaginary")


def _span(name, sid, t0, t1, parent=None, trace_id=1):
    s = {"name": name, "span_id": sid, "trace_id": trace_id, "t0": t0, "t1": t1}
    if parent is not None:
        s["parent_id"] = parent
    return s


def test_self_time_leaves_out_server_children():
    recs = [
        _span("wire.archive_batch", 1, 0.0, 10.0),
        _span("server.archive_batch", 2, 2.0, 5.0, parent=1),
        _span("server.archive_batch", 3, 4.0, 6.0, parent=1),  # overlaps the first
        _span("fdb.archive_batch", 4, 2.5, 4.5, parent=2),
        _span("wire.retrieve_batch", 5, 20.0, 21.0, trace_id=2),
    ]
    wire = spans.self_seconds(recs, lambda n: n == "wire.archive_batch",
                              lambda n: n.startswith("server."))
    assert wire == pytest.approx(10.0 - 4.0)
    assert spans.union_seconds([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4.0)
    assert spans.traces_of(recs, "wire.retrieve_batch") == {2}
