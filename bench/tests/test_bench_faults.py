"""The comparison that decides ``correct`` fails the control and every
fault the cells can have, planted under a run that is otherwise whole.

The control is the reference computed in bfloat16, put in the program's
place on the same sampled fields.  The faults: an answer altered where it
is produced (the decoder moves part of a field), fields packed at another
width than their tier states, writes acknowledged but never stored, and
stale answers (the step before the one asked for, under its own keys or
under the keys asked for).
"""

from __future__ import annotations

import time

import pytest

import benchtiny

WORKLOADS = ["ens-0p1.wr", "hammer-1mib.wr", "hammer-1mib.read", "ens-0p1.archive"]
#: the cells whose requests reach a step after a cycle's first, which a
#: stale answer needs: readers alone read the prefill's first step
STALE_WORKLOADS = ["ens-0p1.wr", "hammer-1mib.wr"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "")


def _measure(root, workload, *, tree=None):
    from fdbbench.harness import measure
    from fdbbench.spec import load_cell

    cell = load_cell(root, workload)
    return cell, measure(cell, 3, 1.0, trace=False, devices=benchtiny.cpu_devices(1),
                         t_start=time.perf_counter(), tree=tree)


def _failed(checks: dict) -> list[str]:
    return [k for k, (v, lim) in checks.items() if not v <= lim]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_fails_and_program_passes_on_the_same_fields(root, workload):
    from fdbbench.harness import compare

    _, o = _measure(root, workload)
    assert _failed(compare(o)) == []
    failed = _failed(compare(o, control=True))
    assert "gap16" in failed and "offgrid16" in failed


@pytest.mark.parametrize("workload", WORKLOADS)
def test_altered_answers_are_not_correct(root, workload):
    from fdbbench import faults

    with faults.altered_answers():
        rc, line, err = benchtiny.run_main(root, workload, seed=4)
    assert rc == 0, err
    assert line["correct"] is False
    assert line["checks"]["gap16"]["value"] > line["checks"]["gap16"]["limit"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_fields_packed_at_another_width_are_not_correct(root, workload):
    from fdbbench import faults
    from fdbbench.harness import compare

    cell, _ = _measure(root, workload)
    _, o = _measure(root, workload, tree=faults.swapped_widths(cell.config)["tree"])
    assert "offgrid16" in _failed(compare(o))


@pytest.mark.parametrize("workload", STALE_WORKLOADS)
@pytest.mark.parametrize("relabel", [False, True], ids=["own-keys", "asked-keys"])
def test_stale_answers_are_not_correct(root, workload, relabel):
    from fdbbench import faults

    with faults.stale_reads(relabel=relabel):
        rc, line, err = benchtiny.run_main(root, workload, seed=2**31 + 17, seconds=1.5)
    assert rc == 0, err
    assert line["correct"] is False
    checks = line["checks"]
    if relabel:
        # on whichever tier the stale answers were sampled
        assert any(c["value"] > 100 * c["limit"] for k, c in checks.items() if k.startswith("gap"))
    else:
        assert checks["missing"]["value"] > 0 and "answered keys" in err


def test_acknowledged_writes_that_are_lost_are_not_correct(root, monkeypatch):
    """Once the window opens, the wire client acknowledges archives without
    sending them: readers and the read-back find the fields missing."""
    from fdbbench.loop import Loop

    from repro.core.remote import RemoteFDB

    run = Loop.run

    def run_with_lost_writes(self, seconds, **kw):
        monkeypatch.setattr(RemoteFDB, "archive_batch", lambda self, items: None)
        return run(self, seconds, **kw)

    monkeypatch.setattr(Loop, "run", run_with_lost_writes)
    rc, line, err = benchtiny.run_main(root, "hammer-1mib.wr", seed=9, seconds=1.5)
    assert rc == 0, err
    assert line["correct"] is False
    assert line["failed"] > 0 and line["checks"]["missing"]["value"] > 0
