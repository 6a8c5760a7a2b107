"""The generator: its shared state under stress (more reader threads than
cores, a short switch interval, and a writer that wipes a cycle after
every step: a reader must never find its step wiped under it, and every
reader must leave the cycle it entered), the sources of each step, and
the step a writer has in flight when the window closes."""

from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np
import pytest

import benchtiny


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "")


def test_wipes_never_race_readers(tmp_path):
    from fdbbench.harness import compare, measure
    from fdbbench.spec import load_cell

    root = benchtiny.make_root(tmp_path)
    cell = load_cell(root, "hammer-1mib.wr")
    traffic = benchtiny.traffic([0, 1], 2, 16, "one_field", "uniform")
    traffic["retention"] = {"steps_per_cycle": 1, "cycles": 2}
    cell = dataclasses.replace(cell, traffic=traffic)
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        o = measure(cell, 21, 1.5, trace=False, devices=benchtiny.cpu_devices(1),
                    t_start=time.perf_counter())
    finally:
        sys.setswitchinterval(before)
    assert o.loop.failures == []
    assert all(n == 0 for n in o.loop._reading.values())
    reads = o.loop.window_records("retrieve")
    assert len(reads) > 16 and all(r.ok for r in reads)
    # more cycles were written than retention keeps, so wipes happened
    assert max(c for c, _, _ in o.loop._cursor.values()) >= 2
    assert all(v <= lim for v, lim in compare(o).values())


@pytest.mark.parametrize("grid", [[16, 128], [512, 1024], [1801, 3600]])
def test_every_step_has_sources_of_its_own(grid):
    """Two writers' first 1024 steps each take a roll no other step takes;
    a roll moves every value and keeps the field's values."""
    from fdbbench.loop import Plan

    cfg = benchtiny.config(benchtiny.SINGLE, [0, 1], {"t": [250.0, 20.0]}, [1, 2])
    plan = Plan({**cfg, "grid": grid}, benchtiny.traffic([0, 1], 2, 2, "one_field", "uniform"))
    assert len({plan.shift(w, n) for w in range(2) for n in range(1024)}) == 2 * 1024
    f = np.arange(2 * grid[0] * grid[1], dtype=np.float32).reshape(2, *grid)
    rolled = plan.source(f, plan.shift(1, 0))
    assert rolled.shape == f.shape and not (rolled == f).any()
    assert np.array_equal(np.sort(rolled, axis=None), np.sort(f, axis=None))


def test_writers_finish_the_step_in_flight(tmp_path):
    """At the close a writer completes its step, flush included: every
    byte archived in the window belongs to a step the read-back can reach."""
    from fdbbench.harness import measure
    from fdbbench.spec import load_cell

    root = benchtiny.make_root(tmp_path)
    cell = load_cell(root, "ens-0p1.wr")
    o = measure(cell, 2**32 + 3, 1.0, trace=False, devices=benchtiny.cpu_devices(1),
                t_start=time.perf_counter())
    plan = o.plan
    calls_per_step = plan.step_size // plan.fields_per_call
    steps = {m: done for m, (_, _, done) in o.loop._cursor.items()}
    archived = len(o.loop.window_records("archive"))
    assert archived == (sum(steps.values()) - plan.prefill_steps * len(steps)) * calls_per_step
    assert all(steps[m] - plan.prefill_steps >= 1 for m in steps)
    assert o.loop.failures == []
