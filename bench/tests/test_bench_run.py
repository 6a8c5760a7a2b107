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


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """The harness turns JAX's persistent cache on for the chip; in the test
    process that would leak into every later test of the worker."""
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "")


@pytest.mark.parametrize("workload", ["ens-0p1.wr", "hammer-1mib.wr"])
def test_untraced_run_reports_end_to_end_metrics(root, workload):
    rc, line, err = benchtiny.run_main(root, workload, seed=2**31 + 5, seconds=1.0)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    expected = {"archive_GBps", "retrieve_GBps", "setup_s"}
    if workload == "hammer-1mib.wr":
        expected.add("retrieve_p95_ms")
    assert set(line["metrics"]) == expected
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["metrics"]["archive_GBps"]["unit"] == "GB/s"
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    # the numbers compared end the standard error, each beside its limit
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and "(limit " in t for t in tail)
    # set-up warmed every program the window runs
    assert " lowerings_in_window 0 " in err


@pytest.mark.parametrize("workload", ["ens-0p1.wr", "hammer-1mib.wr"])
def test_traced_run_reports_per_layer_metrics(root, workload):
    rc, line, err = benchtiny.run_main(root, workload, seed=11, seconds=1.0, trace=1)
    assert rc == 0, err
    assert line["correct"] is True, err
    # the CPU trace has no device plane: the kernel rooflines find nothing
    # to read and stay out of the line; every span reader reports
    expected = {"codec_pack_s_per_GB", "codec_unpack_s_per_GB", "wire_s_per_GB.archive",
                "wire_s_per_GB.retrieve"}
    if workload == "hammer-1mib.wr":
        expected.add("catalogue_ms_per_request")
    assert set(line["metrics"]) == expected
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
