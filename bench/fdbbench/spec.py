"""Find a cell's files by name.

``BENCHMARK.json`` at the root of the checkout names the cells.  A cell's
configuration is the file its ``configs`` entry names; its traffic mix is
``bench/traffic/<traffic>.json``; each per-layer metric is a reader in
``bench/metrics/<metric>.py`` with a ``read(ctx)`` function.  A later
change adds a configuration, a mix or a metric by adding such files and
entries, never by editing this module.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

__all__ = ["Cell", "load_cell", "load_reader", "reports"]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    #: the ``end_to_end`` entries this cell reports, in manifest order
    end_to_end: tuple
    #: the ``per_layer`` entries this cell reports, in manifest order
    per_layer: tuple
    root: Path


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports ``metric``: listed in its ``workloads``, or
    every cell without that key."""
    return cell in metric.get("workloads", (cell,))


def load_cell(root: Path, workload: str) -> Cell:
    root = Path(root)
    manifest = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = tuple(m for m in manifest["end_to_end"] if reports(m, workload))
    per_layer = tuple(m for m in manifest["per_layer"] if reports(m, workload))
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer, root)


def load_reader(root: Path, metric: str) -> Callable:
    """The ``read`` function of ``bench/metrics/<metric>.py``.  Metric names
    may hold dots, so the file is loaded by path, not imported by name."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
