"""The whole-column cell (``ens-0p1-l137.read``) on the CPU at a tiny grid.

Every request reads all levels of one parameter; the wire's part bound is
lowered here so that each reply comes in several parts, as the 1.78 and
3.55 GB replies of the real cell do.  The run is the chip's: set-up, the
window, the comparison with the reference and the result line, with the
stream readers in the traced line.
"""

from __future__ import annotations

import json
import time

import pytest

import benchtiny

CELL = "ens-0p1-l137.read"
LEVELS = list(range(1, 13))
STREAM_READERS = {"stream_recv_s_per_GB", "stream_serve_s_per_GB", "stream_wait_s_per_GB"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = benchtiny.make_root(tmp_path_factory.mktemp("bench-stream"))
    benchtiny.write_json(root / "bench" / "configs" / "tiny-column.json",
                         benchtiny.config(benchtiny.TIERED, [0, 1], {"t": [250.0, 20.0]}, LEVELS))
    benchtiny.write_json(root / "bench" / "traffic" / "tiny-column.json", benchtiny.readers_only(
        benchtiny.traffic([0, 1], 1, 2, "param_levels", "alternate")))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-column", "source": "test",
                                "file": "bench/configs/tiny-column.json", "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-column", "traffic": "tiny-column",
                                  "chips": 1, "why": "test"})
    benchtiny.write_json(root / "BENCHMARK.json", manifest)
    return root


@pytest.fixture(autouse=True)
def small_parts(monkeypatch):
    """A part bound of three 16-bit fields of the tiny grid: 4 parts a hot
    reply, 12 a cold one (a 24-bit field alone is past half the bound)."""
    import repro.compile_cache
    from repro.core.remote import protocol as P

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "")
    field = P.reply_item_bytes(P.Op.RETRIEVE_BATCH, None, 32 + 2 * benchtiny.GRID[0] * benchtiny.GRID[1])
    monkeypatch.setattr(P, "PART_BYTES", 9 + 3 * field)


def test_untraced_column_run_is_correct(root):
    rc, line, err = benchtiny.run_main(root, CELL, seed=2**31 + 7, seconds=1.0)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"retrieve_GBps", "setup_s"}
    assert " lowerings_in_window 0 " in err


def test_traced_column_run_reports_the_stream_readers(root):
    rc, line, err = benchtiny.run_main(root, CELL, seed=13, seconds=1.0, trace=1)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert set(line["metrics"]) == STREAM_READERS
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_every_column_reply_comes_in_parts(root):
    from fdbbench.harness import measure
    from fdbbench.spec import load_cell

    o = measure(load_cell(root, CELL), 2**31 + 43, 1.0, trace=True,
                devices=benchtiny.cpu_devices(1), t_start=time.perf_counter())
    reads = [s for s in o.spans if s["name"] == "wire.retrieve_batch"]
    assert len(reads) == len(o.loop.window_records("retrieve")) > 0
    parts = sorted(s["attrs"]["parts"] for s in reads)
    assert set(parts) <= {4, 12} and parts[0] == 4
    served = [s for s in o.spans if s["name"] == "server.retrieve_batch.part"]
    received = [s for s in o.spans if s["name"] == "wire.recv_part"]
    assert len(served) == len(received) == sum(parts)
    # a part is whole fields: every level of the reply, once
    assert sum(s["attrs"]["fields"] for s in received) == len(LEVELS) * len(reads)


def _measure(root, *, tree=None):
    from fdbbench.harness import measure
    from fdbbench.spec import load_cell

    cell = load_cell(root, CELL)
    return cell, measure(cell, 5, 1.0, trace=False, devices=benchtiny.cpu_devices(1),
                         t_start=time.perf_counter(), tree=tree)


def _failed(checks: dict) -> list[str]:
    return [k for k, (v, lim) in checks.items() if not v <= lim]


def test_the_control_fails_where_the_streamed_program_passes(root):
    from fdbbench.harness import compare

    _, o = _measure(root)
    # the read-back takes every level of both members: whole columns
    assert len(o.loop.samples) >= 2 * len(LEVELS)
    assert _failed(compare(o)) == []
    failed = _failed(compare(o, control=True))
    assert "gap16" in failed and "offgrid16" in failed


def test_streamed_answers_altered_in_any_part_are_not_correct(root):
    from fdbbench import faults

    with faults.altered_answers():
        rc, line, err = benchtiny.run_main(root, CELL, seed=4)
    assert rc == 0, err
    assert line["correct"] is False
    assert line["checks"]["gap16"]["value"] > line["checks"]["gap16"]["limit"]


def test_streamed_fields_packed_at_another_width_are_not_correct(root):
    from fdbbench import faults
    from fdbbench.harness import compare

    cell, _ = _measure(root)
    _, o = _measure(root, tree=faults.swapped_widths(cell.config)["tree"])
    assert "offgrid16" in _failed(compare(o))
