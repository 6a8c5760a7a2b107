"""A benchmark root at a tiny size, for the CPU tests of the harness.

``make_root`` lays out ``BENCHMARK.json`` and ``bench/`` in a directory of
the test's own: the harness, readers and peaks of this checkout, and one
tiny configuration and traffic mix per kind of cell (a tiered pair of
served codec tiers, and one served DAOS tier, each under writers and
readers and under one side alone), with a ``cpu`` entry in the peaks so
the harness runs on JAX's CPU backend.
"""

from __future__ import annotations

import contextlib
import io
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

GRID = [16, 128]

TIERED = {
    "type": "select",
    "rules": [{"match": "number=0",
               "fdb": {"type": "codec", "nbits": 16,
                       "inner": {"type": "remote", "inner": {"backend": "daos", "schema": "nwp-daos"}}}}],
    "default": {"type": "codec", "nbits": 24,
                "inner": {"type": "remote",
                          "inner": {"backend": "posix", "schema": "nwp-posix", "root": "{scratch}/cold"}}},
}
SINGLE = {"type": "codec", "nbits": 16,
          "inner": {"type": "remote", "pool_size": 4, "inner": {"backend": "daos", "schema": "nwp-daos"}}}


def config(tree: dict, members: list[int], params: dict, levels: list[int]) -> dict:
    widths = {"gap16": 4.0, "offgrid16": 10.0}
    if tree["type"] == "select":
        widths["gap24"] = 16.0
    return {
        "grid": GRID, "dtype": "float32", "first_date": "20240601",
        "dataset": {"class": "od", "stream": "enfo", "expver": "0001", "time": "0000",
                    "type": "pf", "levtype": "ml"},
        "members": members, "params": params, "levels": levels, "tree": tree,
        "limits": {**widths, "missing": 0},
    }


def traffic(writers: list[int], fpc: int, readers: int, request: str, pick: str) -> dict:
    return {
        "loop": "closed",
        "writers": {"members": writers, "fields_per_call": fpc},
        "retention": {"steps_per_cycle": 2, "cycles": 2},
        "readers": {"count": readers, "member": pick, "request": request},
        "prefill_steps": 1, "pool_steps": 2, "sample_fields": 6, "readback_per_member": 1,
    }


def readers_only(mix: dict) -> dict:
    """``mix`` with its writers archiving the prefill alone, as
    ``hammer-read`` is ``hammer-wr``."""
    return {**mix, "writers": {**mix["writers"], "window": False}}


def writers_only(mix: dict) -> dict:
    """``mix`` with no readers and two read-backs a member, as
    ``ens-archive`` is ``ens-wr``."""
    return {**mix, "readers": {**mix["readers"], "count": 0}, "readback_per_member": 2}


def manifest() -> dict:
    """The checkout's BENCHMARK.json, its cells and configurations pointed
    at the tiny files."""
    with open(BENCH.parent / "BENCHMARK.json") as f:
        m = json.load(f)
    m["configs"] = [
        {"name": "tiny-tiered", "source": "test", "file": "bench/configs/tiny-tiered.json",
         "reduced": [], "why": "test"},
        {"name": "tiny-daos", "source": "test", "file": "bench/configs/tiny-daos.json",
         "reduced": [], "why": "test"},
    ]
    m["workloads"] = [
        {"name": "ens-0p1.wr", "config": "tiny-tiered", "traffic": "tiny-wr", "chips": 1, "why": "test"},
        {"name": "hammer-1mib.wr", "config": "tiny-daos", "traffic": "tiny-hammer", "chips": 1,
         "why": "test"},
        {"name": "hammer-1mib.read", "config": "tiny-daos", "traffic": "tiny-hammer-read", "chips": 1,
         "why": "test"},
        {"name": "ens-0p1.archive", "config": "tiny-tiered", "traffic": "tiny-archive", "chips": 1,
         "why": "test"},
    ]
    return m


def write_json(path: Path, doc: dict) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(doc, indent=1))


def make_root(tmp: Path) -> Path:
    root = Path(tmp) / "checkout"
    bench = root / "bench"
    bench.mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = {"hbm_bytes_per_s": 1e10, "bf16_flops_per_s": 1e11}
    write_json(bench / "peaks.json", peaks)
    write_json(bench / "configs" / "tiny-tiered.json",
               config(TIERED, [0, 1], {"t": [250.0, 20.0], "u": [0.0, 12.0]}, [1, 2]))
    write_json(bench / "configs" / "tiny-daos.json",
               config(SINGLE, [0, 1], {"130": [250.0, 20.0], "133": [0.004, 0.002]}, [0, 1]))
    write_json(bench / "traffic" / "tiny-wr.json", traffic([0, 1], 2, 2, "param_levels", "alternate"))
    write_json(bench / "traffic" / "tiny-hammer.json", traffic([0, 1], 2, 2, "one_field", "uniform"))
    write_json(bench / "traffic" / "tiny-hammer-read.json", readers_only(
        traffic([0, 1], 2, 2, "one_field", "uniform")))
    write_json(bench / "traffic" / "tiny-archive.json", writers_only(
        traffic([0, 1], 2, 2, "param_levels", "alternate")))
    write_json(root / "BENCHMARK.json", manifest())
    return root


def cpu_devices(chips: int) -> list:
    import jax

    return jax.devices()[:chips]


def run_main(root: Path, workload: str, *, seed: int = 7, seconds: float = 1.0, trace: int = 0,
             device_check=cpu_devices) -> tuple[int, dict | None, str]:
    """``bench/run.py``'s body on the CPU; returns its exit code, the
    parsed last line of its output (None when it printed nothing) and its
    standard error."""
    from fdbbench.harness import main

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", str(trace)], root=root, t_start=time.perf_counter(),
                  device_check=device_check)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
