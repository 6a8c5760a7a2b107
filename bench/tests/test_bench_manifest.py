"""``BENCHMARK.json`` keeps to the names and shapes the benchmark's
contract allows, and every file it points at is where the harness looks."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

import benchtiny  # noqa: F401  (puts the harness on the path)
from fdbbench.loop import Plan, tier_nbits

ROOT = benchtiny.BENCH.parent
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51 and isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("section", list(KEYS))
def test_entries_use_only_allowed_keys_and_characters(section):
    entries = MANIFEST[section]
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e["name"]
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e and section in ("configs", "workloads", "per_layer") and k != "source":
                assert _line(e[k]), (e["name"], k)
    metric_names = {m["name"] for s in ("end_to_end", "per_layer") for m in MANIFEST[s]}
    assert len(metric_names) == sum(len(MANIFEST[s]) for s in ("end_to_end", "per_layer"))


def test_configurations_and_cells():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    used = set()
    seen_pairs = set()
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4)
        assert w["config"] in configs
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in seen_pairs
        seen_pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert used == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    for c in configs.values():
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        doc = json.loads((ROOT / c["file"]).read_text())
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_reports_what_its_metrics_move(cell):
    from fdbbench.spec import load_cell

    c = load_cell(ROOT, cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in MANIFEST["per_layer"]:
        if cell in m.get("workloads", ()):
            assert m["moves"] in e2e, (m["name"], cell)
    for m in c.per_layer:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_metric_fields():
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in SOURCES_E2E
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    layers: dict[str, str] = {}
    for m in MANIFEST["per_layer"]:
        assert m["source"] in SOURCES
        assert m["moves"] in e2e
        assert _line(m["layer"])
        layers.setdefault(m["layer"].lower(), m["layer"])
        assert layers[m["layer"].lower()] == m["layer"]
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_width_a_cell_packs_at_has_limits(cell):
    from fdbbench.spec import load_cell

    c = load_cell(ROOT, cell)
    plan = Plan(c.config, c.traffic)
    widths = {tier_nbits(plan.tree, plan.key(m, "", 0, *plan.step_fields[0]))
              for m in plan.writers}
    for n in widths:
        assert f"gap{n}" in c.config["limits"]
    assert c.config["limits"]["missing"] == 0
    assert Path(ROOT / "bench" / "peaks.json").is_file()
