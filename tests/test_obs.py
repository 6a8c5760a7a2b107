"""Distributed-tracing tests (repro.obs + the instrumented facades).

Covers the tracer core (parents, links, ring, watchdog, adopt), the two
structural properties every trace must satisfy (resolvable parents, child
intervals nested inside their parents'), the Chrome trace-event export and
its CI validator, the ``"trace"`` config option, and the stitched
cross-process traces the ISSUE names as acceptance:

- a traced ``retrieve_many`` through SelectFDB-over-RemoteFDB yields client
  AND server spans sharing one trace id;
- a traced ``archive_fields`` round through an async client against a live
  FDBServer serving a tiered codec config yields ONE trace holding the tier
  routing, the codec kernel launches, the async queue wait, the wire round
  and the server-side backend time;
- with tracing disabled (the default) the instrumented hot paths allocate
  NOTHING inside the obs module (tracemalloc-guarded).
"""

import json
import threading
import tracemalloc
from collections import Counter

import numpy as np
import pytest

from repro.core import (
    AsyncFDB,
    FDBServer,
    NWP_SCHEMA_POSIX,
    RemoteFDB,
    SelectFDB,
    build_fdb,
    make_fdb,
)
from repro.core.config import ConfigError, FDBConfig
from repro.obs import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    chrome_trace,
    install_tracer,
    make_tracer,
    validate_chrome_trace,
    write_chrome_trace,
    write_jsonl,
)
from test_select import ident, make_bare


def base_key(i: int = 0, number: int = 0) -> dict:
    return dict(ident(num=str(number), step=str(i)))


def populate_fields(n: int = 4, h: int = 8, w: int = 128):
    """n distinct fields spread over two ensemble members (numbers 0 and 1,
    so a number=0 select rule splits them across tiers) and n//2 steps."""
    keys = [base_key(i // 2, number=i % 2) for i in range(n)]
    rng = np.random.default_rng(7)
    fields = (rng.standard_normal((n, h, w)) * 40 + 250).astype(np.float32)
    return keys, fields


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

class TestTracerCore:
    def test_nesting_and_parents(self):
        tr = Tracer()
        with tr.span("a") as a:
            with tr.span("b") as b:
                assert b.parent_id == a.span_id
                assert b.trace_id == a.trace_id
            with tr.span("c") as c:
                assert c.parent_id == a.span_id
        assert a.parent_id is None
        names = [s.name for s in tr.spans()]
        assert names == ["b", "c", "a"]  # finish order

    def test_explicit_root_and_cross_thread_parent(self):
        tr = Tracer()
        with tr.span("outer") as outer:
            with tr.span("forced-root", parent=None) as root:
                assert root.parent_id is None
                assert root.trace_id != outer.trace_id
            ctx = outer.context
            done = []

            def worker():
                with tr.span("child", parent=ctx) as ch:
                    done.append((ch.trace_id, ch.parent_id))

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert done == [(outer.trace_id, outer.span_id)]

    def test_link_shares_trace_without_containment(self):
        tr = Tracer()
        with tr.span("enqueue") as enq:
            ctx = enq.context
        with tr.span("exec", parent=None, link=ctx) as ex:
            pass
        assert ex.trace_id == enq.trace_id
        assert ex.parent_id is None
        assert ex.link_id == enq.span_id

    def test_error_attr_on_exception(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        (sp,) = tr.spans()
        assert sp.attrs["error"] == "RuntimeError"

    def test_ring_capacity_and_drain(self):
        tr = Tracer(capacity=4)
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        names = [s.name for s in tr.spans()]
        assert names == ["s6", "s7", "s8", "s9"]
        assert len(tr.drain()) == 4
        assert tr.spans() == []

    def test_virtual_clock(self):
        t = [0.0]
        tr = Tracer(clock=lambda: t[0])
        with tr.span("op") as sp:
            t[0] = 2.5
        assert sp.t0 == 0.0 and sp.t1 == 2.5
        assert sp.duration_s == 2.5

    def test_slow_op_watchdog_captures_full_tree(self):
        t = [0.0]
        tr = Tracer(clock=lambda: t[0], slow_op_s=1.0)
        with tr.span("root"):
            with tr.span("child"):
                t[0] = 0.2
            t[0] = 1.5
        with tr.span("fast"):
            pass
        assert len(tr.slow_ops) == 1
        slow = tr.slow_ops[0]
        assert slow["root"] == "root" and slow["duration_s"] == 1.5
        assert {s["name"] for s in slow["spans"]} == {"root", "child"}

    def test_adopt_preserves_ids_and_times(self):
        src, dst = Tracer(proc="server"), Tracer(proc="client")
        with src.span("remote-op") as sp:
            sp.set("k", 1)
        n = dst.adopt([s.to_dict() for s in src.drain()])
        assert n == 1
        (got,) = dst.spans()
        assert (got.span_id, got.trace_id, got.t0, got.t1, got.proc) == (
            sp.span_id, sp.trace_id, sp.t0, sp.t1, "server",
        )
        assert got.attrs == {"k": 1}

    def test_make_tracer(self):
        tr = make_tracer(True)
        assert isinstance(tr, Tracer) and tr.proc == "client"
        tr = make_tracer({"capacity": 8, "slow_op_s": 0.5, "proc": "cell"})
        assert tr.slow_op_s == 0.5 and tr.proc == "cell"
        with pytest.raises(TypeError):
            make_tracer(3)

    def test_null_tracer_is_inert(self):
        assert not NULL_TRACER.enabled
        sp = NULL_TRACER.span("anything")
        with sp as s:
            s.set("k", "v")
        assert sp is NULL_TRACER.span("other")  # the singleton
        assert sp.context is None
        assert NULL_TRACER.spans() == [] and NULL_TRACER.drain() == []
        assert NULL_TRACER.adopt([{"name": "x"}]) == 0
        assert isinstance(NULL_TRACER, NullTracer)


# ---------------------------------------------------------------------------
# structural properties of real traces
# ---------------------------------------------------------------------------

def check_trace_structure(spans, *, eps: float = 1e-9) -> None:
    """The two invariants every exported trace must satisfy:

    1. every ``parent_id``/``link_id`` resolves to a span in the set;
    2. a child's interval nests inside its parent's interval.

    (Cross-process parents are timed on different clocks, so interval
    nesting is only asserted for same-proc parent/child pairs.)
    """
    by_id = {s.span_id: s for s in spans}
    assert len(by_id) == len(spans), "span ids must be unique"
    for s in spans:
        assert s.t1 is not None and s.t1 >= s.t0
        if s.parent_id is not None:
            parent = by_id.get(s.parent_id)
            assert parent is not None, f"{s.name}: dangling parent {s.parent_id:#x}"
            assert parent.trace_id == s.trace_id
            if parent.proc == s.proc:
                assert parent.t0 - eps <= s.t0, f"{s.name} starts before {parent.name}"
                assert s.t1 <= parent.t1 + eps, f"{s.name} ends after {parent.name}"
        if s.link_id is not None:
            link = by_id.get(s.link_id)
            assert link is not None, f"{s.name}: dangling link {s.link_id:#x}"
            assert link.trace_id == s.trace_id


class TestTraceStructure:
    def test_local_composed_tree(self, tmp_path):
        """Batch ops through async-over-select-over-posix: every span's
        parent resolves and every child nests inside its parent."""
        hot = make_bare("posix", tmp_path, "hot")
        cold = make_bare("posix", tmp_path, "cold")
        fdb = AsyncFDB(
            SelectFDB([("number=0", hot)], default=cold),
            writers=2, batch_size=4,
        )
        tr = Tracer()
        assert install_tracer(fdb, tr) >= 4  # async, select, 2 tiers
        try:
            keys, fields = populate_fields(6)
            fdb.archive_fields(keys, fields)
            fdb.flush()
            got = fdb.retrieve_fields(dict(keys[0])).arrays()
            assert got.shape[0] >= 1
        finally:
            fdb.close()
        spans = tr.spans()
        assert len(spans) > 10
        check_trace_structure(spans)
        names = {s.name for s in spans}
        assert "codec.pack" in names
        assert "async.archive_batch" in names
        assert {"select.archive_batch", "select.tier_archive"} <= names

    def test_async_link_carries_queue_wait(self, tmp_path):
        fdb = AsyncFDB(make_bare("posix", tmp_path, "q"), writers=1, batch_size=8)
        tr = Tracer()
        install_tracer(fdb, tr)
        try:
            for i in range(4):
                fdb.archive(base_key(i), b"z" * 64)
            fdb.drain()
        finally:
            fdb.close()
        spans = tr.spans()
        check_trace_structure(spans)
        execs = [s for s in spans if s.name == "async.archive_batch"]
        enqs = {s.span_id: s for s in spans if s.name == "async.enqueue"}
        assert execs and enqs
        for ex in execs:
            assert ex.link_id in enqs  # follows-from the enqueue span
            assert ex.trace_id == enqs[ex.link_id].trace_id
            assert ex.attrs["queue_wait_max_s"] >= 0.0


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

class TestExport:
    def _spans(self):
        tr = Tracer(proc="cellA")
        with tr.span("root") as root:
            with tr.span("inner") as sp:
                sp.set("bytes", 42)
            ctx = root.context
        with tr.span("follow", parent=None, link=ctx):
            pass
        return tr.spans()

    def test_chrome_trace_validates(self, tmp_path):
        spans = self._spans()
        doc = chrome_trace(spans)
        n = validate_chrome_trace(doc)
        assert n == len(doc["traceEvents"])
        phases = [e["ph"] for e in doc["traceEvents"]]
        assert phases.count("X") == 3
        assert "s" in phases and "f" in phases  # the flow pair for the link
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   and e["args"]["name"] == "cellA" for e in doc["traceEvents"])
        # round-trips through a file, and through span dicts
        path = tmp_path / "trace.json"
        assert write_chrome_trace(str(path), spans) == n
        assert validate_chrome_trace(json.loads(path.read_text())) == n
        assert validate_chrome_trace(
            chrome_trace([s.to_dict() for s in spans])
        ) == n

    def test_jsonl_export(self, tmp_path):
        spans = self._spans()
        path = tmp_path / "trace.jsonl"
        assert write_jsonl(str(path), spans) == 3
        recs = [json.loads(line) for line in path.read_text().splitlines()]
        assert [r["name"] for r in recs] == ["inner", "root", "follow"]
        assert recs[2]["link_id"] == recs[1]["span_id"]

    def test_validator_rejects_malformed(self):
        with pytest.raises(ValueError, match="traceEvents"):
            validate_chrome_trace({})
        with pytest.raises(ValueError, match="phase"):
            validate_chrome_trace({"traceEvents": [{"ph": "Q", "name": "x",
                                                    "pid": 1, "tid": 1, "ts": 0}]})
        with pytest.raises(ValueError, match="dur"):
            validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "x",
                                                    "pid": 1, "tid": 1, "ts": 0}]})
        with pytest.raises(ValueError, match="ts"):
            validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "x", "dur": 1,
                                                    "pid": 1, "tid": 1, "ts": -5}]})


# ---------------------------------------------------------------------------
# the "trace" config option
# ---------------------------------------------------------------------------

class TestTraceConfig:
    def test_build_fdb_installs_tracer(self, tmp_path):
        fdb = build_fdb({
            "type": "select",
            "rules": [],
            "default": {"backend": "posix", "root": str(tmp_path / "t"),
                        "schema": "nwp-posix"},
            "trace": {"capacity": 512, "slow_op_s": 9.0},
        })
        try:
            assert isinstance(fdb.tracer, Tracer)
            assert fdb.tracer.slow_op_s == 9.0
            # the SAME tracer reached the tier below the select facade
            assert all(t.tracer is fdb.tracer for t in fdb.tiers)
            fdb.archive(base_key(), b"p" * 32)
            fdb.flush()
            assert any(s.name == "select.archive" for s in fdb.tracer.spans())
        finally:
            fdb.close()

    def test_trace_false_and_absent_stay_null(self, tmp_path):
        for extra in ({}, {"trace": False}):
            fdb = build_fdb({"backend": "posix", "root": str(tmp_path / "n"),
                             "schema": "nwp-posix", **extra})
            try:
                assert fdb.tracer is NULL_TRACER
            finally:
                fdb.close()

    def test_validation_rejects_bad_specs(self, tmp_path):
        base = {"backend": "posix", "root": str(tmp_path), "schema": "nwp-posix"}
        for bad in ({"capacitee": 1}, {"capacity": 0}, {"slow_op_s": -1},
                    "yes", 3):
            with pytest.raises(ConfigError):
                FDBConfig({**base, "trace": bad})
        FDBConfig({**base, "trace": True})  # and the good ones pass
        FDBConfig({**base, "trace": {"capacity": 16, "proc": "x"}})


# ---------------------------------------------------------------------------
# stitched cross-process traces (the ISSUE's acceptance shapes)
# ---------------------------------------------------------------------------

@pytest.fixture
def servers():
    started = []
    yield started
    for s in started:
        s.stop()


def start_server(servers, cfg) -> str:
    server = FDBServer(cfg)
    host, port = server.start()
    servers.append(server)
    return f"{host}:{port}"


class TestStitchedTraces:
    def test_select_over_remote_retrieve_many(self, servers, tmp_path):
        """Traced retrieve_many through SelectFDB-over-RemoteFDB: client and
        server spans share one trace id."""
        addr = start_server(servers, {"backend": "posix",
                                      "root": str(tmp_path / "srv"),
                                      "schema": "nwp-posix"})
        remote = RemoteFDB(addr)
        fdb = SelectFDB([("class=od", remote)])
        tr = Tracer()
        install_tracer(fdb, tr)
        try:
            keys = [base_key(i) for i in range(3)]
            for k in keys:
                fdb.archive(k, b"d" * 128)
            fdb.flush()
            remote.fetch_server_trace()  # drain the archive-phase spans …
            tr.clear()  # … so only the retrieve trace is under test
            datas = fdb.retrieve_many(dict(keys[0])).read_all()
            assert all(v == b"d" * 128 for v in datas.values())
            remote.fetch_server_trace()
        finally:
            fdb.close()
        spans = tr.spans()
        check_trace_structure(spans)
        by_trace = {}
        for s in spans:
            by_trace.setdefault(s.trace_id, []).append(s)
        stitched = [
            grp for grp in by_trace.values()
            if {"client", "server"} <= {s.proc for s in grp}
        ]
        assert stitched, "no trace contains both client and server spans"
        names = {s.name for grp in stitched for s in grp}
        assert any(n.startswith("wire.") for n in names)
        assert any(n.startswith("server.") for n in names)

    def test_v1_peer_interop_no_trace_flag(self, servers, tmp_path, monkeypatch):
        """A client negotiated down to ext level 1 must never send traced
        frames — and still works with tracing on (spans stay client-only)."""
        addr = start_server(servers, {"backend": "posix",
                                      "root": str(tmp_path / "v1"),
                                      "schema": "nwp-posix"})
        from repro.core.remote import protocol as P

        # pretend the server answered a bare v1 HELLO (no trailing ext)
        monkeypatch.setattr(P, "decode_hello_ext", lambda cur: 1)
        fdb = RemoteFDB(addr)
        tr = Tracer()
        install_tracer(fdb, tr)
        try:
            fdb.archive(base_key(), b"x" * 16)
            fdb.flush()
            assert fdb.read(base_key()) == b"x" * 16
        finally:
            fdb.close()
        spans = tr.spans()
        assert spans and all(s.proc == "client" for s in spans)

    def test_full_acceptance_round(self, servers, tmp_path):
        """The ISSUE's acceptance shape: a traced ``archive_fields`` round
        from an async client against a live FDBServer serving a tiered codec
        config yields ONE stitched trace holding the tier routing, the codec
        kernel launches, the async queue wait, the wire rounds and the
        server-side backend time."""
        addr = start_server(servers, {
            "type": "select",
            "rules": [{"match": "number=0",
                       "fdb": {"type": "codec", "nbits": 16,
                               "inner": {"backend": "posix",
                                         "root": str(tmp_path / "hot"),
                                         "schema": "nwp-posix"}}}],
            "default": {"type": "codec", "nbits": 24,
                        "inner": {"backend": "posix",
                                  "root": str(tmp_path / "cold"),
                                  "schema": "nwp-posix"}},
        })
        remote = RemoteFDB(addr)
        fdb = AsyncFDB(remote, writers=2, batch_size=4, owns_fdb=True)
        tr = Tracer()
        install_tracer(fdb, tr)
        try:
            keys, fields = populate_fields(6)
            fdb.archive_fields(keys, fields)
            fdb.flush()
            req = {**{k: v for k, v in keys[0].items()
                      if k not in ("step", "number")},
                   "step": sorted({k["step"] for k in keys}),
                   "number": ["0", "1"]}
            got = fdb.retrieve_fields(req).arrays()
            assert got.shape == fields.shape
            remote.fetch_server_trace()
        finally:
            fdb.close()

        spans = tr.spans()
        check_trace_structure(spans)

        # the archive round is ONE trace: root the client archive_fields span
        roots = [s for s in spans if s.name == "client.archive_fields"]
        assert len(roots) == 1
        tid = roots[0].trace_id
        trace = [s for s in spans if s.trace_id == tid]
        names = {s.name for s in trace}
        procs = {s.proc for s in trace}
        assert procs == {"client", "server"}
        # codec kernel launch (client side, before the wire)
        assert "codec.pack" in names
        pack = next(s for s in trace if s.name == "codec.pack")
        assert pack.attrs["effective_bytes"] > pack.attrs["wire_bytes"]
        # async queue wait, linked (follows-from) to the enqueue spans
        execs = [s for s in trace if s.name == "async.archive_batch"]
        assert execs and all(s.link_id is not None for s in execs)
        assert all(s.attrs["queue_wait_max_s"] >= 0.0 for s in execs)
        # the wire round and the server-side spans beneath it
        assert "wire.archive_batch" in names
        assert "server.archive_batch" in names
        # tier routing on the SERVER, attributed under the client's trace
        assert "select.archive_batch" in names
        tier_spans = [s for s in trace if s.name == "select.tier_archive"]
        assert tier_spans and all(s.proc == "server" for s in tier_spans)
        # backend time on the server
        assert {"fdb.archive_batch", "store.archive_batch",
                "catalogue.archive_batch"} <= names


def traced_served_round() -> list:
    """One archive call of four fields, a flush and a one-field retrieve
    through a traced codec tier over a self-hosted wire, each under a root
    span of its own (``req.archive``, ``req.flush``, ``req.retrieve``);
    returns every span, the server's included."""
    fdb = build_fdb({"type": "codec", "nbits": 16, "trace": True,
                     "inner": {"type": "remote",
                               "inner": {"backend": "daos", "schema": "nwp-daos"}}})
    keys = [base_key(0) | {"levelist": str(lv)} for lv in range(4)]
    fields = populate_fields(4)[1]
    tr = fdb.tracer
    try:
        with tr.span("req.archive", parent=None):
            fdb.archive_fields(keys, fields)
        with tr.span("req.flush", parent=None):
            fdb.flush()
        with tr.span("req.retrieve", parent=None):
            assert fdb.retrieve_fields(keys[1]).arrays().shape == (1, 8, 128)
    finally:
        fdb.close()  # hands over the server's spans
    return tr.spans()


class TestWireSpans:
    def test_send_and_recv_are_children_of_the_wire_span(self):
        spans = traced_served_round()
        check_trace_structure(spans)
        wire = [s for s in spans if s.name.startswith("wire.")
                and s.name not in ("wire.send", "wire.recv")]
        assert {"wire.archive_batch", "wire.flush", "wire.retrieve_many"} <= {
            s.name for s in wire}
        for w in wire:
            kids = sorted((s for s in spans if s.parent_id == w.span_id and s.proc == "client"),
                          key=lambda s: s.t0)
            assert [s.name for s in kids] == ["wire.send", "wire.recv"], w.name
            assert w.t0 <= kids[0].t0 <= kids[0].t1 <= kids[1].t0 <= kids[1].t1 <= w.t1
            assert all(s.thread_id == w.thread_id for s in kids)

    def test_traced_ops_carry_their_wait_for_a_server_thread(self):
        spans = traced_served_round()
        served = {s.name: s for s in spans if s.name.startswith("server.")}
        assert {"server.archive_batch", "server.flush", "server.retrieve_many"} <= set(served)
        for s in served.values():
            assert s.attrs["queued_s"] >= 0.0
        by_id = {s.span_id: s for s in spans}
        assert by_id[served["server.retrieve_many"].parent_id].name == "wire.retrieve_many"

    def test_a_read_on_the_loop_keeps_its_span(self):
        """The round's one-field retrieve runs on the server's event loop:
        its span carries ``inline`` beside ``queued_s``, the request still
        leaves 5 server spans, and the stitched trace holds."""
        spans = traced_served_round()
        check_trace_structure(spans)
        served = [s for s in spans if s.name.startswith("server.")]
        inline = [s for s in served if (s.attrs or {}).get("inline")]
        assert [s.name for s in inline] == ["server.retrieve_many"]
        assert inline[0].attrs["queued_s"] >= 0.0
        by_id = {s.span_id: s for s in spans}
        assert by_id[inline[0].parent_id].name == "wire.retrieve_many"
        server_spans = [s for s in spans if s.proc == "server"
                        and s.trace_id == inline[0].trace_id]
        assert len(server_spans) == 5

    def test_traced_ops_carry_their_frame_read(self):
        spans = traced_served_round()
        served = [s for s in spans if s.name.startswith("server.")]
        assert "server.archive_batch" in {s.name for s in served}
        for s in served:
            assert s.attrs["read_s"] >= 0.0 and s.attrs["read_calls"] >= 1, s.name

    def test_untraced_server_counts_its_frame_reads(self, tmp_path):
        with build_fdb({"type": "remote",
                        "inner": {"backend": "posix", "root": str(tmp_path / "w")}}) as fdb:
            fdb.archive(base_key(), b"r" * 4096)
            fdb.flush()
            wire = fdb.server_stats()["wire"]
        assert wire["ops"]["wire_frame_read"] >= 3  # hello, archive, flush
        assert wire["op_bytes_r"]["wire_frame_read"] > 4096
        assert wire["op_time"]["wire_frame_read"] >= 0.0

    def test_server_spans_per_traced_request_stay_put(self):
        """A server keeps its spans in a ring of its own until its client
        fetches them; the spans each request leaves there are counted, so a
        new server span cannot fill the ring unnoticed."""
        spans = traced_served_round()
        roots = {s.trace_id: s.name for s in spans if s.name.startswith("req.")}
        per_request = Counter(roots[s.trace_id] for s in spans
                              if s.proc == "server" and s.trace_id in roots)
        assert per_request == {"req.archive": 4, "req.flush": 4, "req.retrieve": 5}


# ---------------------------------------------------------------------------
# zero cost when disabled
# ---------------------------------------------------------------------------

class TestDisabledOverhead:
    def test_no_obs_allocations_when_disabled(self, tmp_path):
        """With the default NULL_TRACER, a full archive/retrieve round must
        allocate NOTHING inside the obs module (the null span is one
        process-wide singleton)."""
        fdb = make_fdb("posix", schema=NWP_SCHEMA_POSIX,
                       root=str(tmp_path / "z"))
        assert fdb.tracer is NULL_TRACER
        keys = [base_key(i) for i in range(4)]
        payload = b"w" * 256

        def one_round():
            fdb.archive_batch([(k, payload) for k in keys])
            fdb.flush()
            assert all(d is not None for d in fdb.read_batch(keys))

        try:
            one_round()  # warm every lazy path (dirs, caches, interning)
            obs_filter = tracemalloc.Filter(True, "*/repro/obs/*")
            tracemalloc.start(25)
            try:
                before = tracemalloc.take_snapshot().filter_traces([obs_filter])
                one_round()
                after = tracemalloc.take_snapshot().filter_traces([obs_filter])
            finally:
                tracemalloc.stop()
        finally:
            fdb.close()
        diff = after.compare_to(before, "lineno")
        grew = [d for d in diff if d.size_diff > 0 or d.count_diff > 0]
        assert not grew, f"obs allocations on the disabled hot path: {grew}"

    def test_enabled_then_disabled_again(self, tmp_path):
        """install_tracer(NULL_TRACER) switches a tree back off."""
        fdb = make_fdb("posix", schema=NWP_SCHEMA_POSIX,
                       root=str(tmp_path / "t"))
        tr = Tracer()
        install_tracer(fdb, tr)
        fdb.archive(base_key(), b"a")
        n = len(tr.spans())
        assert n > 0
        install_tracer(fdb, NULL_TRACER)
        fdb.archive(base_key(1), b"b")
        assert len(tr.spans()) == n
        fdb.close()
