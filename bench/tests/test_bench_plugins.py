"""A configuration, a traffic mix and a per-layer metric are added by new
files and new ``BENCHMARK.json`` entries alone: the harness finds them by
name, and no file already under ``bench/`` changes."""

from __future__ import annotations

import hashlib
import json

import pytest

import benchtiny

NEW_METRIC = '''"""Wire calls per reader request."""


def read(ctx):
    n = len(ctx.requests("retrieve"))
    calls = [s for s in ctx.spans if s["name"].startswith("wire.")]
    return len(calls) / n if n and calls else None
'''


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "")


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_cell_from_new_files_alone(tmp_path):
    root = benchtiny.make_root(tmp_path)
    before = _digests(root)

    bench = root / "bench"
    benchtiny.write_json(bench / "configs" / "tiny-single-posix.json", benchtiny.config(
        {"type": "codec", "nbits": 16,
         "inner": {"type": "remote", "inner": {"backend": "posix", "schema": "nwp-posix",
                                               "root": "{scratch}/posix"}}},
        [3, 4], {"v": [0.0, 12.0]}, [10, 20, 30]))
    mix = benchtiny.traffic([3, 4], 3, 1, "param_levels", "uniform")
    mix["readers"]["count"] = 3
    benchtiny.write_json(bench / "traffic" / "tiny-read-heavy.json", mix)
    (bench / "metrics" / "wire_calls_per_request.py").write_text(NEW_METRIC)

    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny-single-posix", "source": "test",
                                "file": "bench/configs/tiny-single-posix.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "tiny-posix.rh", "config": "tiny-single-posix",
                                  "traffic": "tiny-read-heavy", "chips": 1, "why": "test"})
    manifest["per_layer"].append({"name": "wire_calls_per_request", "unit": "calls",
                                  "better": "lower", "source": "program_span",
                                  "layer": "wire", "moves": "retrieve_GBps",
                                  "workloads": ["tiny-posix.rh"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before

    rc, line, err = benchtiny.run_main(root, "tiny-posix.rh", seconds=1.0, trace=1)
    assert rc == 0, err
    assert line["correct"] is True, err
    assert line["metrics"]["wire_calls_per_request"]["value"] > 0
    assert line["metrics"]["wire_calls_per_request"]["unit"] == "calls"
    # the metrics of the other cells stay theirs
    assert "catalogue_ms_per_request" not in line["metrics"]


def test_cells_report_what_they_are_listed_for(tmp_path):
    from fdbbench.spec import load_cell

    root = benchtiny.make_root(tmp_path)
    ens = load_cell(root, "ens-0p1.wr")
    ham = load_cell(root, "hammer-1mib.wr")
    assert {m["name"] for m in ens.end_to_end} == {"archive_GBps", "retrieve_GBps", "setup_s"}
    assert {m["name"] for m in ham.end_to_end} == {"archive_GBps", "retrieve_GBps",
                                                    "retrieve_p95_ms", "setup_s"}
    assert "catalogue_ms_per_request" in {m["name"] for m in ham.per_layer}
    assert "catalogue_ms_per_request" not in {m["name"] for m in ens.per_layer}
    with pytest.raises(KeyError, match="no workload"):
        load_cell(root, "no-such.cell")


def test_metric_without_a_cell_list_is_read_in_every_cell():
    from fdbbench.spec import reports

    assert reports({"name": "x", "moves": "retrieve_p95_ms"}, "any")
    assert reports({"name": "y", "workloads": ["a"]}, "a")
    assert not reports({"name": "y", "workloads": ["a"]}, "b")
