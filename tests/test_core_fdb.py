"""FDB semantics tests — both backends must satisfy the paper's §1.3 contract."""

import os
import threading

import pytest

from repro.core import FDB, Key, NWP_SCHEMA_DAOS, NWP_SCHEMA_POSIX, make_fdb
from repro.core.daos import DaosEngine


def example_key(**over) -> Key:
    base = dict(
        # dataset
        **{"class": "od"}, stream="oper", expver="0001", date="20231201", time="1200",
        # collocation (DAOS schema)
        type="ef", levtype="sfc", number="1", levelist="1",
        # element
        step="1", param="v",
    )
    base.update(over)
    return Key(base)


@pytest.fixture(params=["daos", "posix"])
def fdb(request, tmp_path):
    if request.param == "daos":
        yield make_fdb("daos", schema=NWP_SCHEMA_DAOS, engine=DaosEngine())
    else:
        yield make_fdb("posix", schema=NWP_SCHEMA_POSIX, root=str(tmp_path / "fdb"))


class TestSemantics:
    def test_archive_then_flush_then_retrieve(self, fdb):
        fdb.archive(example_key(), b"field-bytes-0")
        fdb.flush()
        assert fdb.read(example_key()) == b"field-bytes-0"

    def test_absent_field_is_none_not_error(self, fdb):
        assert fdb.read(example_key(param="zz")) is None

    def test_flush_publishes_everything_archived(self, fdb):
        keys = [example_key(step=str(s), param=p) for s in range(4) for p in ("u", "v")]
        for i, k in enumerate(keys):
            fdb.archive(k, f"payload-{i}".encode())
        fdb.flush()
        for i, k in enumerate(keys):
            assert fdb.read(k) == f"payload-{i}".encode()

    def test_replacement_is_transactional(self, fdb):
        k = example_key()
        fdb.archive(k, b"old")
        fdb.flush()
        fdb.archive(k, b"new")
        fdb.flush()
        assert fdb.read(k) == b"new"

    def test_old_data_visible_until_new_flushed_posix(self, tmp_path):
        # POSIX backend defers visibility to flush(): the old value must stay
        # visible while the replacement is archived-but-not-flushed.
        writer = make_fdb("posix", schema=NWP_SCHEMA_POSIX, root=str(tmp_path / "f"))
        reader = make_fdb("posix", schema=NWP_SCHEMA_POSIX, root=str(tmp_path / "f"))
        k = example_key()
        writer.archive(k, b"old")
        writer.flush()
        writer.archive(k, b"new")  # NOT flushed yet
        assert reader.read(k) == b"old"
        writer.flush()
        assert reader.read(k) == b"new"

    def test_daos_immediate_visibility(self):
        # DAOS publishes at archive() time (flush is a no-op) — paper §3.1.2.
        eng = DaosEngine()
        writer = make_fdb("daos", schema=NWP_SCHEMA_DAOS, engine=eng)
        reader = make_fdb("daos", schema=NWP_SCHEMA_DAOS, engine=eng)
        k = example_key()
        writer.archive(k, b"immediately-visible")
        assert reader.read(k) == b"immediately-visible"

    def test_list_partial_request(self, fdb):
        for s in range(3):
            for p in ("u", "v", "t"):
                fdb.archive(example_key(step=str(s), param=p), b"x")
        fdb.flush()
        entries = list(fdb.list({"step": "1"}))
        assert len(entries) == 3
        assert {e.key["param"] for e in entries} == {"u", "v", "t"}
        # span request
        entries = list(fdb.list({"param": ["u", "t"], "step": ["0", "2"]}))
        assert len(entries) == 4

    def test_list_reflects_replacement_once(self, fdb):
        k = example_key()
        fdb.archive(k, b"v1")
        fdb.flush()
        fdb.archive(k, b"v2")
        fdb.flush()
        entries = [e for e in fdb.list({"param": "v"}) if e.key == k]
        assert len(entries) == 1
        h = fdb.store.retrieve(entries[0].location)
        assert h.read() == b"v2"

    def test_wipe_dataset(self, fdb):
        fdb.archive(example_key(), b"x")
        fdb.flush()
        fdb.wipe(example_key())
        assert fdb.read(example_key()) is None
        assert list(fdb.list({})) == []

    def test_datahandle_ranged_read(self, fdb):
        fdb.archive(example_key(), b"0123456789")
        fdb.flush()
        h = fdb.retrieve(example_key())
        assert h.size == 10
        assert h.read_range(3, 4) == b"3456"


class TestContention:
    """Writer/reader contention — the paper's central scenario."""

    def test_concurrent_writers_distinct_fields(self, fdb):
        errs = []

        def writer(member: int):
            try:
                for step in range(8):
                    fdb.archive(example_key(number=str(member), step=str(step)), f"m{member}s{step}".encode())
                fdb.flush()
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(m,)) for m in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        for m in range(8):
            for s in range(8):
                assert fdb.read(example_key(number=str(m), step=str(s))) == f"m{m}s{s}".encode()

    def test_reader_never_sees_torn_state_daos(self):
        # Readers racing a writer must see either nothing or the full field.
        eng = DaosEngine()
        writer = make_fdb("daos", schema=NWP_SCHEMA_DAOS, engine=eng)
        reader = make_fdb("daos", schema=NWP_SCHEMA_DAOS, engine=eng)
        payload = bytes(range(256)) * 64
        stop = threading.Event()
        bad = []

        def read_loop():
            while not stop.is_set():
                for s in range(16):
                    got = reader.read(example_key(step=str(s)))
                    if got is not None and got != payload:
                        bad.append((s, len(got)))

        t = threading.Thread(target=read_loop)
        t.start()
        for s in range(16):
            writer.archive(example_key(step=str(s)), payload)
        writer.flush()
        stop.set()
        t.join()
        assert not bad


class TestDaosEmulation:
    def test_mvcc_versions_accumulate(self):
        from repro.core.daos.objects import KVObject, ObjectId

        kv = KVObject(ObjectId(0, 1))
        kv.put("k", b"1")
        kv.put("k", b"2")
        assert kv.get("k") == b"2"
        assert kv.version_count("k") == 2  # old version retained, not modified

    def test_array_extents_latest_epoch_wins(self):
        from repro.core.daos.objects import ArrayObject, ObjectId

        arr = ArrayObject(ObjectId(1, 1))
        arr.write(0, b"aaaaaaaa")
        arr.write(4, b"bbbb")
        assert arr.read(0, 8) == b"aaaabbbb"
        assert arr.get_size() == 8

    def test_array_read_of_one_extent(self):
        from repro.core.daos.objects import ArrayObject, ObjectId

        arr = ArrayObject(ObjectId(1, 2))
        arr.write(0, bytearray(b"0123456789"))
        assert arr.read() == arr.read(0, 10) == b"0123456789"
        assert type(arr.read()) is bytes
        assert arr.read(3, 4) == b"3456"
        assert arr.read(8, 4) == b"89\x00\x00"

    def test_oid_ranges_do_not_collide_across_threads(self):
        eng = DaosEngine()
        eng.create_pool("p")
        eng.cont_create("p", "c")
        from repro.core.daos_backend.store import OidAllocator

        allocs = [OidAllocator(eng, "p", "c", batch=16) for _ in range(4)]
        seen = set()
        lock = threading.Lock()

        def run(a):
            for _ in range(200):
                oid = a.next_oid()
                with lock:
                    assert oid not in seen
                    seen.add(oid)

        ts = [threading.Thread(target=run, args=(a,)) for a in allocs]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(seen) == 800

    def test_stats_accounting(self):
        eng = DaosEngine()
        fdb = make_fdb("daos", schema=NWP_SCHEMA_DAOS, engine=eng)
        fdb.archive(example_key(), b"x" * 1024)
        fdb.flush()
        snap = eng.stats.snapshot()
        assert snap["ops"]["daos_array_write"] == 1
        assert snap["ops"]["daos_kv_put"] >= 1
        assert snap["bytes_written"] >= 1024


class TestSchema:
    def test_split_matches_paper_example(self):
        split = NWP_SCHEMA_DAOS.split(example_key())
        assert dict(split.dataset) == {
            "class": "od", "stream": "oper", "expver": "0001", "date": "20231201", "time": "1200"
        }
        assert dict(split.collocation) == {"type": "ef", "levtype": "sfc", "number": "1", "levelist": "1"}
        assert dict(split.element) == {"step": "1", "param": "v"}

    def test_stringify_roundtrip(self):
        split = NWP_SCHEMA_DAOS.split(example_key())
        s = split.dataset.stringify()
        assert s == "od:oper:0001:20231201:1200"
        back = NWP_SCHEMA_DAOS.dataset_from_string(s)
        assert back == split.dataset

    def test_missing_keyword_rejected(self):
        with pytest.raises(KeyError):
            NWP_SCHEMA_DAOS.split(Key({"class": "od"}))

    def test_posix_daos_schema_levels_differ(self):
        # §5.1: number/levelist at collocation level for DAOS, element for POSIX
        assert "number" in NWP_SCHEMA_DAOS.collocation_keys
        assert "number" in NWP_SCHEMA_POSIX.element_keys


def _hammer_child(member: int, sockpath: str):
    # module-level so the 'spawn' start method can pickle it by reference
    from repro.core import NWP_SCHEMA_DAOS, make_fdb
    from repro.core.daos.server import DaosClient

    cli = DaosClient(sockpath)
    fdb = make_fdb("daos", schema=NWP_SCHEMA_DAOS, engine=cli)
    for step in range(4):
        fdb.archive(example_key(number=str(member), step=str(step)), f"m{member}s{step}".encode())
    fdb.flush()
    cli.close()


def test_multiprocess_daos_server(tmp_path):
    """True OS-process contention through the socket-served engine."""
    import multiprocessing as mp

    from repro.core.daos.server import DaosClient, serve_engine

    sock = str(tmp_path / "daos.sock")
    srv = serve_engine(sock)
    try:
        # spawn, not fork: the test process holds JAX's thread pools, and
        # os.fork() from a multithreaded process can deadlock the children
        ctx = mp.get_context("spawn")
        procs = [ctx.Process(target=_hammer_child, args=(m, sock)) for m in range(3)]
        for p in procs:
            p.start()
        for p in procs:
            p.join(timeout=60)
            assert p.exitcode == 0
        cli = DaosClient(sock)
        fdb = make_fdb("daos", schema=NWP_SCHEMA_DAOS, engine=cli)
        for m in range(3):
            for s in range(4):
                assert fdb.read(example_key(number=str(m), step=str(s))) == f"m{m}s{s}".encode()
        cli.close()
    finally:
        srv.stop()
