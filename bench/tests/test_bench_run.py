"""``bench/run.py`` end to end on the CPU at a tiny grid.

The harness's look for a TPU is steered in the test (the CPU's devices
are handed in); everything else is the run the chip makes: set-up, the
closed-loop window, the comparison with the reference and the result line.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import benchtiny

REPO = benchtiny.BENCH.parent

#: the end-to-end metrics each cell reports, and no other
END_TO_END = {
    "ens-0p1.wr": {"archive_GBps", "retrieve_GBps", "setup_s"},
    "hammer-1mib.wr": {"archive_GBps", "retrieve_GBps", "retrieve_p95_ms", "setup_s"},
    "hammer-1mib.read": {"retrieve_GBps", "retrieve_p95_ms", "setup_s"},
    "ens-0p1.archive": {"archive_GBps", "setup_s"},
}

#: the per-layer metrics a CPU trace reads: the span readers of each
#: direction, and of the hammer cells' per-request path
_ARCHIVE = {"codec_pack_s_per_GB", "codec_host_s_per_GB.pack", "wire_s_per_GB.archive",
            "wire_io_s_per_GB.archive"}
_RETRIEVE = {"codec_unpack_s_per_GB", "codec_host_s_per_GB.unpack", "wire_s_per_GB.retrieve",
             "wire_io_s_per_GB.retrieve"}
_PER_REQUEST = {"catalogue_ms_per_request", "serve_queue_ms_per_request"}
PER_LAYER_ON_CPU = {
    "ens-0p1.wr": _ARCHIVE | _RETRIEVE,
    "hammer-1mib.wr": _ARCHIVE | _RETRIEVE | _PER_REQUEST,
    "hammer-1mib.read": _RETRIEVE | _PER_REQUEST,
    "ens-0p1.archive": _ARCHIVE,
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """The harness turns JAX's persistent cache on for the chip; in the test
    process that would leak into every later test of the worker."""
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "")


@pytest.mark.parametrize("workload", list(END_TO_END))
def test_untraced_run_reports_end_to_end_metrics(root, workload):
    rc, line, err = benchtiny.run_main(root, workload, seed=2**31 + 5, seconds=1.0)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == END_TO_END[workload]
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert all(m["unit"] == "GB/s" for k, m in line["metrics"].items() if k.endswith("_GBps"))
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    # the numbers compared end the standard error, each beside its limit
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and "(limit " in t for t in tail)
    # set-up warmed every program the window runs
    assert " lowerings_in_window 0 " in err


@pytest.mark.parametrize("workload", list(PER_LAYER_ON_CPU))
def test_traced_run_reports_per_layer_metrics(root, workload):
    rc, line, err = benchtiny.run_main(root, workload, seed=11, seconds=1.0, trace=1)
    assert rc == 0, err
    assert line["correct"] is True, err
    # the CPU trace has no device plane: the kernel rooflines and the launch
    # costs find nothing to read and stay out of the line; every span
    # reader of the cell's directions reports
    assert set(line["metrics"]) == PER_LAYER_ON_CPU[workload]
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["window_s"] > 0
    assert {"device_ops", "idle_gaps"} <= set(line["breakdown"])


def test_same_seed_same_sources(root):
    import numpy as np

    from fdbbench.fields import make_pool

    a = make_pool(2**33 + 1, [250.0, 0.0], [20.0, 12.0], (16, 128))
    b = make_pool(2**33 + 1, [250.0, 0.0], [20.0, 12.0], (16, 128))
    c = make_pool(2**33 + 2, [250.0, 0.0], [20.0, 12.0], (16, 128))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.float32 and np.isfinite(a).all()


def test_without_a_tpu_exits_nonzero_and_prints_nothing(root):
    from fdbbench.harness import require_tpu

    rc, line, err = benchtiny.run_main(root, "hammer-1mib.wr", device_check=require_tpu)
    assert rc != 0 and line is None
    assert "no TPU" in err


def _run_script(cwd: Path, *args: str) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_script_without_a_tpu_fails_with_no_result():
    res = _run_script(REPO, "--workload", "hammer-1mib.wr", "--seed", "1", "--seconds", "1",
                      "--trace", "0")
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_run_script_alone_with_its_paths_fails(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    has no system under test."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in json.loads((REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_script(tmp_path, "--workload", "ens-0p1.wr", "--seed", "1", "--seconds", "1",
                      "--trace", "0")
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def _small_server_rings(monkeypatch, ring: int, fetch_s: float) -> None:
    """Servers that keep ``ring`` spans, fetched every ``fetch_s`` seconds."""
    import repro.core.remote.server as server
    from fdbbench import harness

    from repro.obs import Tracer

    monkeypatch.setattr(server, "Tracer", lambda **kw: Tracer(capacity=ring, **kw))
    monkeypatch.setattr(harness, "_server_ring", lambda: ring)
    monkeypatch.setattr(harness, "FETCH_S", fetch_s)


def _traced(root, workload):
    import time

    from fdbbench.harness import measure
    from fdbbench.spec import load_cell

    return measure(load_cell(root, workload), 2**31 + 41, 1.0, trace=True,
                   devices=benchtiny.cpu_devices(1), t_start=time.perf_counter())


def test_a_traced_window_keeps_more_server_spans_than_one_ring(root, monkeypatch):
    """The servers' spans are fetched while the window runs, so a window
    that serves more requests than a server's ring holds loses none."""
    _small_server_rings(monkeypatch, 200, 0.02)
    o = _traced(root, "hammer-1mib.read")
    served = [s for s in o.spans if s["proc"] == "server"]
    assert len(served) > 2 * 200
    # every reader request of the window has its server span
    reads = {s["trace_id"] for s in o.spans if s["name"] == "bench.retrieve"}
    assert reads == {s["trace_id"] for s in served if s["trace_id"] in reads}
    assert len(reads) == len(o.loop.window_records("retrieve"))


def test_a_server_ring_that_fills_between_fetches_stops_the_run(root, monkeypatch):
    _small_server_rings(monkeypatch, 200, 1000.0)
    with pytest.raises(RuntimeError, match="filled up between two fetches"):
        _traced(root, "hammer-1mib.read")
