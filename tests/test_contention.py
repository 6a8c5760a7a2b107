"""The paper's central claim, asserted (not just plotted): under the default
cost model on the virtual clock, the hammer ``n_procs`` sweep reproduces the
client-scaling crossover — per-process POSIX/Lustre write bandwidth degrades
monotonically beyond a contention knee while DAOS per-process bandwidth
stays within 20% of its single-client value (paper §4/§5.1, Figs 3/4;
companion paper arXiv:2211.09162)."""

import json
import math
import os
import sys
from dataclasses import replace

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from fdb_hammer import (  # noqa: E402
    IO_MODES,
    HammerSpec,
    churn_sweep,
    make_backend,
    run_hammer,
    run_hammer_contended,
    scaling_sweep,
)

from repro.metrics import LustreContention, make_contention  # noqa: E402
from repro.metrics.contention import _Timeline  # noqa: E402

PROCS = (1, 2, 4, 8, 16, 32)
SPEC = HammerSpec(n_steps=2, n_params=3, n_levels=2)  # 12 fields x 64 KiB per proc


@pytest.fixture(scope="module")
def sweep_results():
    return scaling_sweep(SPEC, procs_list=PROCS, out=None)


def _write_curve(results, backend):
    return [row["write"]["per_proc_GiBps_mean"] for row in results["backends"][backend]["sweep"]]


class TestScalingCrossover:
    def test_posix_degrades_monotonically_beyond_knee(self, sweep_results):
        curve = _write_curve(sweep_results, "posix")
        knee = sweep_results["backends"]["posix"]["knee_n_procs"]
        knee_i = PROCS.index(knee)
        assert knee_i < len(PROCS) - 1, "no degradation observed at all"
        # monotone per-process collapse beyond the knee (2% tolerance for
        # boundary effects of the discrete schedule)
        beyond = curve[knee_i:]
        for a, b in zip(beyond, beyond[1:]):
            assert b <= a * 1.02, f"posix per-proc bw not monotone beyond knee: {curve}"
        # and it is a genuine collapse, not a plateau
        assert beyond[-1] < 0.5 * max(curve)

    def test_daos_stays_within_20pct_of_single_client(self, sweep_results):
        curve = _write_curve(sweep_results, "daos")
        assert min(curve) >= 0.8 * curve[0], f"daos per-proc bw degraded >20%: {curve}"
        # aggregate write bandwidth keeps scaling across targets
        agg = [row["write"]["agg_GiBps"] for row in sweep_results["backends"]["daos"]["sweep"]]
        assert agg[-1] > 10 * agg[0]

    def test_crossover_daos_wins_at_scale_posix_wins_uncontended(self, sweep_results):
        posix, daos = _write_curve(sweep_results, "posix"), _write_curve(sweep_results, "daos")
        # few clients: POSIX (PSM2, private streams) is faster (paper §5.1)
        assert posix[0] > daos[0]
        # many clients: extent-lock contention collapses POSIX below DAOS
        assert daos[-1] > posix[-1]

    def test_analytic_model_agrees_directionally(self, sweep_results):
        """Cross-check against the closed-form bottleneck model in
        repro.simulation.cluster: same story on both curves."""
        for backend, flat in (("posix", False), ("daos", True)):
            ana = [r["per_proc_GiBps"] for r in sweep_results["backends"][backend]["analytic"]]
            if flat:
                assert min(ana) >= 0.8 * ana[0], f"analytic daos not flat: {ana}"
            else:
                assert ana[-1] < 0.7 * max(ana), f"analytic posix does not degrade: {ana}"

    def test_sweep_is_deterministic(self):
        spec = HammerSpec(n_steps=1, n_params=2, n_levels=2)
        r1 = scaling_sweep(spec, procs_list=(1, 4, 8), out=None)
        r2 = scaling_sweep(spec, procs_list=(1, 4, 8), out=None)
        assert json.dumps(r1, sort_keys=True) == json.dumps(r2, sort_keys=True)

    def test_bench_json_contents(self, sweep_results, tmp_path):
        """BENCH_contention.json carries per-backend/per-n_procs aggregate
        bandwidth plus p50/p95/p99 op latencies from the metrics package."""
        out = tmp_path / "BENCH_contention.json"
        scaling_sweep(
            HammerSpec(n_steps=1, n_params=2, n_levels=2), procs_list=(1, 2), out=str(out)
        )
        data = json.loads(out.read_text())
        for backend in ("posix", "daos"):
            rows = data["backends"][backend]["sweep"]
            assert [r["n_procs"] for r in rows] == [1, 2]
            for row in rows:
                for phase in ("write", "read"):
                    assert row[phase]["agg_GiBps"] > 0
                    assert len(row[phase]["per_proc_GiBps"]) == row["n_procs"]
                    lat = row[phase]["latency"]
                    assert lat, "latency percentiles missing"
                    for h in lat.values():
                        assert h["p50_s"] <= h["p95_s"] <= h["p99_s"]
                        assert h["count"] > 0


class TestContentionModelUnits:
    def test_timeline_gap_filling(self):
        tl = _Timeline()
        assert tl.reserve(0.0, 1.0) == (0.0, 1.0)
        assert tl.reserve(0.0, 1.0) == (1.0, 2.0)      # queues behind
        assert tl.reserve(5.0, 1.0) == (5.0, 6.0)      # idle: no wait
        assert tl.reserve(1.5, 1.0) == (2.0, 3.0)      # fills the gap before 5.0
        assert tl.reserve(0.0, 1.0) == (3.0, 4.0)      # earliest remaining gap
        assert tl.reserve(0.0, 2.0) == (6.0, 8.0)      # 1s gaps too small -> after
        tl.prune(6.0)  # whole intervals ending before the horizon are dropped
        assert tl.intervals == [[5.0, 8.0]]

    def test_shared_segment_serialises_writers(self):
        cm = LustreContention()
        a, b = cm.new_client("a"), cm.new_client("b")
        nbytes = 1 << 20
        with cm.bind(a):
            lat_a = cm.write("/f/data", nbytes)
        with cm.bind(b):
            lat_b = cm.write("/f/data", nbytes)
        # b queued behind a's OST service for the same file
        assert lat_b > lat_a
        # independent file: no queueing
        c = cm.new_client("c")
        with cm.bind(c):
            lat_c = cm.write("/f/other", nbytes)
        assert lat_c == pytest.approx(lat_a, rel=0.25)

    def test_daos_burst_overlaps_targets(self):
        # small index inserts: per-op round-trips dominate, so a burst with
        # one completion drain and overlapped per-target service must be far
        # cheaper than synchronous rounds (paper §3.1.2); bulk transfer time
        # (the NIC ceiling) is the same either way
        cm = make_contention("daos")
        one = cm.new_client("one")
        many = cm.new_client("many")
        ops = [("daos_kv_put", t, 100, 0) for t in range(8)]
        with cm.bind(one):
            seq = sum(cm.op(op, t, nw, nr) for op, t, nw, nr in ops)
        cm.reset()
        with cm.bind(many):
            burst = cm.burst(ops)
        assert burst < 0.3 * seq

    def test_virtual_clock_does_not_sleep(self):
        import time

        cm = make_contention("posix")
        with cm.bind(cm.new_client("x")):
            t0 = time.perf_counter()
            total = sum(cm.write("/seg", 1 << 26) for _ in range(100))
        assert total > 1.0          # >1 virtual second injected
        assert time.perf_counter() - t0 < 0.5  # ...in well under real-time


class _CallLog:
    """Records the name of every method called on the wrapped FDB."""

    def __init__(self, inner):
        self.inner, self.calls = inner, []

    def __getattr__(self, name):
        attr = getattr(self.inner, name)

        def call(*args, **kwargs):
            self.calls.append(name)
            return attr(*args, **kwargs)

        return call


class TestHammerMemberProgram:
    """One member program drives the wall-clock threads, the virtual-clock
    scheduler and the churn read phase."""

    @pytest.mark.parametrize("io", ("sync", "batched"))
    def test_both_clocks_issue_the_same_calls(self, io):
        spec = HammerSpec(n_procs=1, n_steps=2, n_params=2, n_levels=2,
                          field_size=2048, io=io, read_mult=2)
        threaded = _CallLog(make_backend("daos"))
        model = make_contention("daos")
        virtual = _CallLog(make_backend("daos", contention=model))
        for mode in ("archive", "retrieve"):
            run_hammer(threaded, spec, mode)
            run_hammer_contended(virtual, spec, mode, model)
        assert threaded.calls == virtual.calls
        per_step = ["archive_batch"] if io == "batched" else ["archive"] * 4
        reads = ["read_batch"] if io == "batched" else ["read"] * 4
        assert threaded.calls == (per_step + ["flush"]) * 2 + reads * 4
        threaded.inner.close()
        virtual.inner.close()

    def test_contended_refuses_async(self):
        model = make_contention("daos")
        fdb = make_backend("daos", contention=model)
        spec = HammerSpec(n_procs=1, n_steps=1, n_params=1, n_levels=1, io="async")
        with pytest.raises(ValueError, match="async"):
            run_hammer_contended(fdb, spec, "archive", model)
        fdb.close()

    def test_scaling_cli_refuses_async(self, monkeypatch, capsys):
        import fdb_hammer

        monkeypatch.setattr(sys, "argv", ["fdb_hammer.py", "--scaling", "--io", "async"])
        with pytest.raises(SystemExit) as exc:
            fdb_hammer.main()
        assert exc.value.code == 2
        assert "--io sync or batched" in capsys.readouterr().err

    @pytest.mark.parametrize("io", IO_MODES)
    def test_run_hammer_raises_on_missing_fields(self, io):
        # member 1 never archived: its reads come back empty, are counted,
        # and the run raises once every thread has finished
        spec = HammerSpec(n_procs=1, n_steps=1, n_params=2, n_levels=2,
                          field_size=2048, io=io)
        fdb = make_backend("daos")
        run_hammer(fdb, spec, "archive")
        with pytest.raises(RuntimeError, match="4 reads"):
            run_hammer(fdb, replace(spec, n_procs=2), "retrieve")
        fdb.close()

    @pytest.mark.parametrize("io", ("sync", "batched"))
    def test_contended_raises_on_missing_fields(self, io):
        spec = HammerSpec(n_procs=1, n_steps=1, n_params=2, n_levels=2,
                          field_size=2048, io=io)
        model = make_contention("daos")
        fdb = make_backend("daos", contention=model)
        run_hammer_contended(fdb, spec, "archive", model)
        with pytest.raises(RuntimeError, match="4 reads"):
            run_hammer_contended(fdb, replace(spec, n_procs=2),
                                 "retrieve", model)
        fdb.close()

    def test_churn_sweep_audit_is_clean_and_deterministic(self):
        """The churn cells at CI's shape: migration moves fields on both
        backends while no read fails and no field is listed twice."""
        spec = HammerSpec(n_steps=3, n_params=2, n_levels=2)
        first = churn_sweep(spec, procs_list=(1, 2), out=None)
        again = churn_sweep(spec, procs_list=(1, 2), out=None)
        assert json.dumps(first, sort_keys=True) == json.dumps(again, sort_keys=True)
        assert first["churn_procs_list"] == [1, 2]
        for backend in ("posix", "daos"):
            rows = first["backends"][f"{backend}+churn"]["sweep"]
            assert [r["n_procs"] for r in rows] == [1, 2]
            for row in rows:
                assert math.isfinite(row["interference_ratio"]), row
                assert row["interference_ratio"] > 0, row
                assert row["failed_reads"] == 0, row
                assert row["duplicate_reads"] == 0, row
                assert row["fields_migrated"] > 0, row
