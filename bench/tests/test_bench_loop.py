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


def _readback_samples(o) -> list:
    """The samples the read-back took: it runs last, one sample a field."""
    n = sum(r.nbytes for r in o.loop.window_records("readback")) // o.plan.field_bytes
    return o.loop.samples[len(o.loop.samples) - n:]


def _widths(plan) -> set[int]:
    from fdbbench.loop import tier_nbits

    return {tier_nbits(plan.tree, plan.key(m, "", 0, *plan.step_fields[0])) for m in plan.writers}


def test_writers_that_only_prefill_leave_the_window_to_readers(tmp_path):
    """``writers.window: false``: the prefill is archived in set-up and no
    writer archives in the window; readers and the read-back draw from the
    prefill on every tier."""
    from fdbbench.harness import compare, measure
    from fdbbench.spec import load_cell

    root = benchtiny.make_root(tmp_path)
    cell = load_cell(root, "ens-0p1.wr")
    mix = benchtiny.readers_only(benchtiny.traffic([0, 1], 2, 4, "one_field", "uniform"))
    cell = dataclasses.replace(cell, traffic=mix)
    o = measure(cell, 2**31 + 29, 1.0, trace=False, devices=benchtiny.cpu_devices(1),
                t_start=time.perf_counter())
    assert o.loop.failures == []
    assert o.loop.window_records("archive") == []
    assert len(o.loop.window_records("prefill")) == 2 * o.plan.step_size // o.plan.fields_per_call
    reads = o.loop.window_records("retrieve")
    assert len(reads) > 16 and all(r.ok for r in reads)
    # every writer still holds only its prefill step
    assert o.loop._cursor == {0: (0, 1, 1), 1: (0, 1, 1)}
    readback = _readback_samples(o)
    assert readback and {nbits for _, _, nbits, _ in readback} == _widths(o.plan) == {16, 24}
    assert all(v <= lim for v, lim in compare(o).values())


def test_a_mix_with_no_readers_samples_every_width_from_the_readback(tmp_path):
    from fdbbench.harness import compare, measure
    from fdbbench.spec import load_cell

    root = benchtiny.make_root(tmp_path)
    cell = load_cell(root, "ens-0p1.archive")
    assert cell.traffic["readers"]["count"] == 0
    o = measure(cell, 2**32 + 31, 1.0, trace=False, devices=benchtiny.cpu_devices(1),
                t_start=time.perf_counter())
    assert o.loop.failures == []
    assert o.loop.window_records("retrieve") == []
    assert len(o.loop.window_records("archive")) > 0
    assert len(o.loop.window_records("readback")) == 2 * len(o.plan.writers)
    readback = _readback_samples(o)
    assert len(readback) == len(o.loop.samples)
    assert {nbits for _, _, nbits, _ in readback} == _widths(o.plan) == {16, 24}
    assert all(v <= lim for v, lim in compare(o).values())


def test_a_window_of_no_clients_is_refused():
    from fdbbench.loop import Plan

    cfg = benchtiny.config(benchtiny.SINGLE, [0, 1], {"t": [250.0, 20.0]}, [1, 2])
    mix = benchtiny.writers_only(benchtiny.readers_only(
        benchtiny.traffic([0, 1], 2, 2, "one_field", "uniform")))
    with pytest.raises(ValueError, match="neither writers nor readers"):
        Plan(cfg, mix)
    mix = benchtiny.traffic([0, 1], 2, 2, "one_field", "uniform")
    mix["writers"]["window"] = "false"
    with pytest.raises(ValueError, match="true or false"):
        Plan(cfg, mix)


#: digests of what the accepted mixes generate: the rolls of each writer's
#: first 64 steps, the keys of its first step, 64 reader requests of each
#: reader, and the pool's bases and spreads (taken before ``writers.window``
#: existed)
GENERATED = {
    "ens-wr": "6b15f044e7105531f122ffea0561913b4c4e80ac5da5bc6a58fe2799be72dea0",
    "hammer-wr": "a499b40b63bc8fb25653c0dc3201afba56ef0c830f89a5a6efadbf3fd7caa671",
}


def _generated(plan) -> str:
    import hashlib
    import json

    rows = [[plan.shift(w, n) for n in range(64)] for w in range(len(plan.writers))]
    rows += [[plan.key(m, plan.date(w, 0), 0, p, lv) for p, lv in plan.step_fields]
             for w, m in enumerate(plan.writers)]
    for r in range(8):
        rng = np.random.default_rng([2**31 + 1, 1, r])
        rows.append([plan.request_for(plan.writers[r % len(plan.writers)], plan.date(0, 0), 0, rng)
                     for _ in range(64)])
    rows.append(plan.pool_spread())
    return hashlib.sha256(json.dumps(rows, sort_keys=True).encode()).hexdigest()


@pytest.mark.parametrize("mix", list(GENERATED))
def test_a_mix_without_the_window_key_generates_as_before(mix):
    import json

    from fdbbench.fields import make_pool
    from fdbbench.loop import Plan

    repo = benchtiny.BENCH
    traffic = json.loads((repo / "traffic" / f"{mix}.json").read_text())
    config = json.loads((repo / "configs" / {"ens-wr": "ens-0p1-tiered.json",
                                             "hammer-wr": "hammer-1mib-daos.json"}[mix]).read_text())
    assert "window" not in traffic["writers"]
    plan = Plan(config, traffic)
    assert plan.write_in_window is True
    assert _generated(plan) == GENERATED[mix]
    for window in (True, False):
        other = Plan(config, {**traffic, "writers": {**traffic["writers"], "window": window}})
        assert _generated(other) == GENERATED[mix]
        assert other.write_in_window is window
    # the pool depends on the seed, the spreads and the grid alone
    tiny = {**config, "grid": [8, 128]}
    pools = [make_pool(2**33 + 5, *Plan(tiny, t).pool_spread(), (8, 128)).tobytes()
             for t in (traffic, {**traffic, "writers": {**traffic["writers"], "window": False}})]
    assert pools[0] == pools[1]
