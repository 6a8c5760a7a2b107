"""RemoteFDB wire-transport tests.

Covers the protocol layer (framing, truncation, version checks), full
client round-trips on both backends, the fault paths the ISSUE names
(server kill mid-request, client timeout, retry-with-backoff), wire-level
request batching on the server, the declarative ``{"type": "remote"}``
config node, and — by subclassing the equivalence suite from
``test_select`` — the property that a SelectFDB tree with one remote tier
is observationally identical to the bare backend.

The server's frame intake is driven by raw sockets: frames fed in pieces,
frames larger than the socket buffers, oversized and cut-short frames, and
pipelining past the per-connection bound.

Plus the satellite regression: a FieldSet fetch returning the wrong number
of handles fails loudly naming the keys (it used to zip short and leave
unresolved sentinels behind), which matters once fetches cross a network
hop.
"""

import os
import socket
import threading
import time
import tracemalloc

import numpy as np
import pytest

import test_select
from repro.core import (
    AsyncFDB,
    FDBConfig,
    FDBServer,
    FieldResolutionError,
    FieldSet,
    Key,
    NWP_SCHEMA_POSIX,
    RemoteError,
    RemoteFDB,
    RemoteTimeout,
    SelectFDB,
    UnknownKeywordError,
    build_fdb,
    make_fdb,
    serve_fdb,
)
from repro.core.remote import ProtocolError
from repro.core.remote import protocol as P
from repro.core.request import Request
from test_select import dataset_req, ident, make_bare, populate


@pytest.fixture
def servers():
    """Track servers started by a test; stop them on teardown."""
    started: list[FDBServer] = []
    yield started
    for s in started:
        s.stop()


def start_server(servers, backend, tmp_path, tag="srv", **kw) -> FDBServer:
    server = FDBServer(make_bare(backend, tmp_path, tag), owns_fdb=True, **kw)
    server.start()
    servers.append(server)
    return server


def connect(server: FDBServer, **kw) -> RemoteFDB:
    host, port = server.addr
    return RemoteFDB(f"{host}:{port}", **kw)


# ---------------------------------------------------------------------------
# Protocol layer
# ---------------------------------------------------------------------------

class TestProtocol:
    def test_frame_roundtrip(self):
        frame = P.encode_frame(7, P.Op.FLUSH, b"xyz")
        assert frame == b"\x00\x00\x00\x08" + b"\x00\x00\x00\x07" + bytes([P.Op.FLUSH]) + b"xyz"
        n = P.frame_length(frame[:4])
        assert n == len(frame) - 4
        req_id, opcode, cur = P.split_frame(frame[4:])
        assert (req_id, opcode) == (7, P.Op.FLUSH)
        assert cur._take(3, "payload") == b"xyz"
        cur.expect_end()

    def test_oversized_frame_rejected_without_allocation(self):
        hdr = (1 << 29).to_bytes(4, "big")
        with pytest.raises(ProtocolError, match="exceeds"):
            P.frame_length(hdr, max_frame=1 << 20)

    def test_cursor_truncation_names_what_was_expected(self):
        cur = P.Cursor(b"\x00\x00\x00\x10short")
        with pytest.raises(ProtocolError, match="key"):
            cur.str_("key")

    def test_trailing_bytes_rejected(self):
        cur = P.Cursor(b"\x01extra")
        cur.u8()
        with pytest.raises(ProtocolError, match="trailing"):
            cur.expect_end()

    def test_hello_version_and_magic(self):
        P.decode_hello(P.Cursor(P.encode_hello()))
        with pytest.raises(ProtocolError, match="magic"):
            P.decode_hello(P.Cursor(b"XXXX\x00\x01"))
        bad = P.MAGIC + (P.PROTOCOL_VERSION + 1).to_bytes(2, "big")
        with pytest.raises(ProtocolError, match="version"):
            P.decode_hello(P.Cursor(bad))

    def test_archive_batch_roundtrip(self):
        items = [(ident(step=str(s)), bytes([s]) * 10) for s in range(3)]
        back = P.decode_archive_batch(P.Cursor(P.encode_archive_batch(items)))
        assert back == items

    def test_request_roundtrip_preserves_spans(self):
        req = Request.parse("retrieve,step=0/to/12/by/6,param=*,number=1/2")
        back = P.decode_request(P.Cursor(P.encode_request(req)))
        assert back.format() == req.format()

    def test_fieldset_and_handles_roundtrip_with_absent(self):
        payloads = [b"abc", None, b""]
        assert P.decode_handles(P.Cursor(P.encode_handles(payloads))) == payloads
        items = [(ident(), b"x"), (ident(step="9"), None)]
        assert P.decode_fieldset(P.Cursor(P.encode_fieldset(items))) == items

    def test_error_roundtrip(self):
        err = P.decode_error(P.Cursor(P.encode_error(KeyError("missing thing"))))
        assert isinstance(err, RemoteError)
        assert err.remote_type == "KeyError"
        assert "missing thing" in str(err)

    def test_remote_timeout_is_both_remote_error_and_timeout(self):
        e = RemoteTimeout("too slow")
        assert isinstance(e, RemoteError) and isinstance(e, TimeoutError)

    @pytest.mark.parametrize("wrap", [bytes, bytearray, memoryview])
    def test_split_frame_reads_any_buffer_alike(self, wrap):
        items = [(ident(step=str(s)), bytes([s]) * 5) for s in range(3)]
        body = P.encode_frame(7, P.Op.ARCHIVE_BATCH, P.encode_archive_batch(items))[4:]
        req_id, opcode, cur = P.split_frame(wrap(bytearray(body)))
        assert (req_id, opcode) == (7, P.Op.ARCHIVE_BATCH)
        assert P.decode_archive_batch(cur) == items
        cur.expect_end()

    def test_decoded_payloads_are_bytes_that_outlive_their_buffer(self):
        items = [(ident(step=str(s)), bytes([65 + s]) * 9) for s in range(3)]
        handles = [b"abc", None, b"defg"]
        for encoded, decode, want in (
            (P.encode_archive_batch(items), P.decode_archive_batch, items),
            (P.encode_handles(handles), P.decode_handles, handles),
        ):
            buf = bytearray(P.encode_frame(1, P.Op.OK, encoded)[4:])
            got = decode(P.split_frame(buf)[2])
            buf[:] = b"\xff" * len(buf)  # the wire buffer is reused
            assert got == want
            payloads = [p[1] if isinstance(p, tuple) else p for p in got]
            assert all(p is None or type(p) is bytes for p in payloads)

    def test_handle_views_read_the_frame_uncopied(self):
        handles = [b"abc", None, b"", b"defg"]
        buf = bytearray(P.encode_frame(1, P.Op.OK, P.encode_handles(handles))[4:])
        got = P.decode_handles(P.split_frame(buf)[2], views=True)
        assert got == handles
        views = [g for g in got if g is not None]
        assert all(type(v) is memoryview and v.readonly for v in views)
        buf[-4:] = b"wxyz"  # a view is the frame's bytes, not a copy of them
        assert got[3] == b"wxyz"

    def test_frames_sent_as_pieces_are_the_joined_frames(self):
        items = [(ident(step=str(s)), np.arange(s + 3, dtype=np.uint16)) for s in range(3)]
        joined = [(k, v.tobytes()) for k, v in items]
        pieces = P.archive_batch_pieces(items)
        frame = b"".join([P.frame_head(9, P.Op.ARCHIVE_BATCH, P.pieces_len(pieces)), *pieces])
        assert frame == P.encode_frame(9, P.Op.ARCHIVE_BATCH, P.encode_archive_batch(joined))
        for op in (P.Op.RETRIEVE_BATCH, P.Op.RETRIEVE_MANY):
            reply = [(ident(step="1"), b"xy"), (ident(step="2"), None)]
            pieces = P.reply_pieces(op, reply)
            body = P.encode_handles([b"xy", None]) if op == P.Op.RETRIEVE_BATCH \
                else P.encode_fieldset(reply)
            assert b"".join(pieces) == body

    def test_a_connection_sends_pieces_past_the_socket_buffer_in_order(self):
        from repro.core.remote.client import _Conn

        a, b = socket.socketpair()
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        rng = np.random.default_rng(1)
        pieces = [b"head", memoryview(b""), rng.integers(0, 255, 300_000, dtype=np.uint8),
                  bytearray(b"mid"), rng.integers(0, 1 << 16, 70_000, dtype=np.uint16)]
        pieces += [bytes([i % 256]) * (i + 1) for i in range(700)]  # more buffers than one sendmsg takes
        want = b"".join(bytes(memoryview(p).cast("B")) for p in pieces)
        got = bytearray()

        def drain():
            while len(got) < len(want):
                got.extend(b.recv(1 << 16))

        t = threading.Thread(target=drain)
        t.start()
        conn = _Conn.__new__(_Conn)
        conn.sock = a
        try:
            conn._send(pieces)
            t.join(timeout=30)
        finally:
            a.close()
            b.close()
        assert bytes(got) == want


# ---------------------------------------------------------------------------
# Round trips on both backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["posix", "daos"])
class TestRemoteRoundTrip:
    def test_archive_flush_read(self, backend, tmp_path, servers):
        server = start_server(servers, backend, tmp_path)
        with connect(server) as fdb:
            keys = populate(fdb)
            for i, k in enumerate(keys):
                assert fdb.read(k) == f"payload-{i}".encode()
            assert fdb.read(ident(param="zz")) is None

    def test_retrieve_batch_preserves_order_and_absent(self, backend, tmp_path, servers):
        server = start_server(servers, backend, tmp_path)
        with connect(server) as fdb:
            items = [(ident(step=str(s)), f"s{s}".encode()) for s in range(3)]
            fdb.archive_batch(items)
            fdb.flush()
            keys = [k for k, _ in items][::-1] + [ident(param="zz")]
            handles = fdb.retrieve_batch(keys)
            assert handles[-1] is None
            assert [h.read() for h in handles[:-1]] == [b"s2", b"s1", b"s0"]

    def test_retrieve_many_full_and_partial(self, backend, tmp_path, servers):
        server = start_server(servers, backend, tmp_path)
        with connect(server) as fdb:
            populate(fdb)
            full = dict(ident())
            full.update(step=["0", "1"], param=["2t", "10u"], number=["0", "1"])
            fs = fdb.retrieve_many(full)
            assert len(fs) == 8 and not fs.missing()
            partial = fdb.retrieve_many(Request.parse("step=0/to/2,param=*")).read_all()
            assert len(partial) == 12
            assert all(v is not None for v in partial.values())

    def test_list_and_wipe(self, backend, tmp_path, servers):
        server = start_server(servers, backend, tmp_path)
        with connect(server) as fdb:
            populate(fdb)
            assert len(list(fdb.list({"step": "1"}))) == 4
            report = fdb.wipe(dataset_req())
            assert report.entries_removed == 12
            assert report.datasets == ("od:oper:0001:20240603:1200",)
            assert list(fdb.list({})) == []

    def test_validation_happens_client_side(self, backend, tmp_path, servers):
        server = start_server(servers, backend, tmp_path)
        with connect(server) as fdb:
            before = dict(fdb.wire_stats.snapshot()["ops"])
            with pytest.raises(KeyError):
                fdb.archive(Key({"class": "od"}), b"x")  # missing keywords
            with pytest.raises(UnknownKeywordError):
                fdb.retrieve_many({"bogus_keyword": "1"})
            with pytest.raises(KeyError, match="dataset keywords"):
                fdb.wipe({"class": "od"})
            with pytest.raises(ValueError, match="narrowing"):
                fdb.wipe({**dataset_req(), "step": "0/to/2"})
            # none of those paid a wire round
            assert dict(fdb.wire_stats.snapshot()["ops"]) == before

    def test_server_side_error_travels_as_remote_error(self, backend, tmp_path, servers):
        server = start_server(servers, backend, tmp_path)
        server.fdb.flush = _boom  # server-side failure, not transport
        with connect(server, retries=2) as fdb_raises:
            before = fdb_raises.wire_stats.snapshot()["ops"].get("remote_retry", 0)
            with pytest.raises(RemoteError, match="synthetic server failure"):
                fdb_raises.flush()
            # an application error must never be retried
            after = fdb_raises.wire_stats.snapshot()["ops"].get("remote_retry", 0)
            assert after == before
            del server.fdb.flush  # restore for close()

    def test_wire_telemetry_both_sides(self, backend, tmp_path, servers):
        server = start_server(servers, backend, tmp_path)
        with connect(server) as fdb:
            populate(fdb)
            fdb.read(ident())
            client_ops = fdb.wire_stats.snapshot()["ops"]
            assert client_ops["archive_batch"] >= 1
            assert client_ops["flush"] >= 1
            assert client_ops["retrieve_batch"] >= 1
            snap = server.wire_stats.snapshot()
            assert snap["ops"]["wire_archive_batch"] >= 1
            assert snap["bytes_read"] > 0  # wire bytes in
            assert snap["shard_ops"], "per-connection shards missing"
            stats = fdb.server_stats()
            assert "server" in stats and "wire" in stats

    def test_stats_roundtrip_merges_backend_telemetry(self, backend, tmp_path, servers):
        server = start_server(servers, backend, tmp_path)
        with connect(server) as fdb:
            populate(fdb)
            assert fdb.server_stats()["server"].get("bytes_written", 0) > 0


def _boom():
    raise RuntimeError("synthetic server failure")


# ---------------------------------------------------------------------------
# Fault paths
# ---------------------------------------------------------------------------

class TestFaults:
    def test_connect_to_dead_port_fails_bounded(self, tmp_path):
        # grab a port with no listener behind it
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        t0 = time.perf_counter()
        with pytest.raises(OSError):
            RemoteFDB(f"127.0.0.1:{port}", retries=1, backoff=0.01, timeout=1.0)
        assert time.perf_counter() - t0 < 10.0

    def test_client_timeout_surfaces_as_remote_timeout(self, tmp_path, servers):
        gate = threading.Event()
        server = start_server(servers, "posix", tmp_path)
        server.fdb.flush = gate.wait  # wedge the op server-side
        try:
            with pytest.raises(RemoteTimeout):
                fdb = connect(server, timeout=0.4, retries=0)
                try:
                    fdb.flush()
                finally:
                    fdb._closed = True  # skip close()'s flush on the wedged server
        finally:
            gate.set()
            del server.fdb.flush

    def test_timeout_retry_with_backoff_is_bounded(self, tmp_path, servers):
        """retry-with-backoff on timeout: every attempt times out, the call
        fails after exactly retries+1 attempts, and the retries show up in
        the wire telemetry."""
        gate = threading.Event()
        server = start_server(servers, "posix", tmp_path)
        server.fdb.flush = gate.wait
        try:
            fdb = connect(server, timeout=0.3, retries=2, backoff=0.01)
            t0 = time.perf_counter()
            with pytest.raises(RemoteTimeout, match="after 3 attempts"):
                fdb.flush()
            assert time.perf_counter() - t0 < 5.0
            assert fdb.wire_stats.snapshot()["ops"]["remote_retry"] == 2
            fdb._closed = True
        finally:
            gate.set()
            del server.fdb.flush

    def test_retry_recovers_from_torn_connection(self, tmp_path, servers):
        """A dead pooled socket (server restarted, LB reset, ...) must cost
        one retry, not a failure: the op re-sends on a fresh connection."""
        server = start_server(servers, "posix", tmp_path)
        fdb = connect(server, pool_size=1, retries=2, backoff=0.01)
        populate(fdb)
        # tear the pooled connection under the client
        conn = fdb._pool.get()
        conn.sock.shutdown(socket.SHUT_RDWR)
        conn.sock.close()
        fdb._pool.put(conn)
        assert fdb.read(ident()) == b"payload-0"  # retried transparently
        assert fdb.wire_stats.snapshot()["ops"]["remote_retry"] >= 1
        assert fdb.wire_stats.snapshot()["ops"]["remote_connect"] >= 2
        fdb.close()

    def test_server_kill_mid_request_is_clean_error_not_hang(self, tmp_path):
        """Stopping the server while a request is in flight must surface a
        transport error to the client promptly — never a hang."""
        gate = threading.Event()
        inner = make_fdb("posix", schema=NWP_SCHEMA_POSIX, root=str(tmp_path / "k"))
        inner.flush = gate.wait  # the in-flight op never completes
        server = FDBServer(inner)
        server.start()
        fdb = connect(server, timeout=30.0, retries=0)
        outcome: list = []

        def call():
            try:
                fdb.flush()
                outcome.append("returned")
            except Exception as e:  # noqa: BLE001 — the assertion target
                outcome.append(e)

        t = threading.Thread(target=call)
        t.start()
        time.sleep(0.3)  # let the flush frame reach the wedged server
        server.stop()
        t.join(timeout=10)
        gate.set()
        assert not t.is_alive(), "client hung after server kill"
        assert len(outcome) == 1 and isinstance(outcome[0], (OSError, ProtocolError)), outcome
        fdb._closed = True

    def test_duplicate_hello_rejected_but_connection_survives_app_errors(
        self, tmp_path, servers
    ):
        server = start_server(servers, "posix", tmp_path)
        with connect(server, pool_size=1) as fdb:
            conn = fdb._pool.get()
            op, cur, _ = conn.call(99, P.Op.HELLO, P.encode_hello())
            assert op == P.Op.ERR
            assert "handshake" in str(P.decode_error(cur))
            fdb._pool.put(conn)
            fdb.flush()  # same pool still serves real ops


# ---------------------------------------------------------------------------
# Wire-level batching + backpressure (raw pipelined client)
# ---------------------------------------------------------------------------

class _RawClient:
    """A protocol-speaking socket that can pipeline frames — the pooled
    RemoteFDB never pipelines on one connection, so the server's coalescing
    and backpressure paths need a raw client to exercise them."""

    def __init__(self, addr):
        self.sock = socket.create_connection(addr, timeout=30)
        self.sock.sendall(P.encode_frame(0, P.Op.HELLO, P.encode_hello()))
        req_id, op, _ = self.recv()
        assert (req_id, op) == (0, P.Op.OK)

    def send(self, req_id, opcode, payload=b""):
        self.sock.sendall(P.encode_frame(req_id, opcode, payload))

    def recv(self):
        buf = b""
        while len(buf) < 4:
            buf += self.sock.recv(4 - len(buf))
        n = P.frame_length(buf)
        body = b""
        while len(body) < n:
            body += self.sock.recv(n - len(body))
        return P.split_frame(body)

    def close(self):
        self.sock.close()


class TestWireBatching:
    def test_pipelined_archives_coalesce_into_one_backend_batch(
        self, tmp_path, servers
    ):
        server = start_server(servers, "posix", tmp_path, coalesce=16)
        gate = threading.Event()
        real_list = server.fdb.list
        server.fdb.list = lambda req: (gate.wait(10), real_list(req))[1]
        calls: list[int] = []
        inner_archive = server.fdb.archive_batch
        server.fdb.archive_batch = lambda items: (
            calls.append(len(items)), inner_archive(items))[-1]
        raw = _RawClient(server.addr)
        n = 6
        # wedge the worker on a gated LIST so every archive frame is queued
        # behind it by the time the worker gets to them
        raw.send(1, P.Op.LIST, P.encode_request(Request({"step": "0"})))
        for i in range(n):
            items = [(ident(step=str(i), param=p), f"{i}{p}".encode())
                     for p in ("2t", "10u")]
            raw.send(10 + i, P.Op.ARCHIVE_BATCH, P.encode_archive_batch(items))
        raw.send(99, P.Op.FLUSH)
        time.sleep(0.3)  # reader drains the socket into the frame queue
        gate.set()
        got = {}
        for _ in range(n + 2):
            req_id, op, _ = raw.recv()
            got[req_id] = op
        raw.close()
        assert got == {1: P.Op.OK, 99: P.Op.OK,
                       **{10 + i: P.Op.OK for i in range(n)}}
        # all n queued frames merged into ONE backend archive_batch round
        assert calls == [n * 2]
        assert server.wire_stats.snapshot()["ops"].get("wire_coalesced_frames", 0) >= 1
        del server.fdb.list
        server.fdb.archive_batch = inner_archive
        with connect(server) as check:
            check.flush()
            assert check.read(ident(step="3")) == b"32t"

    def test_bounded_inflight_queue_does_not_deadlock(self, tmp_path, servers):
        server = start_server(servers, "posix", tmp_path, max_inflight=2)
        raw = _RawClient(server.addr)
        n = 20
        for i in range(n):
            raw.send(i, P.Op.ARCHIVE_BATCH,
                     P.encode_archive_batch([(ident(step=str(i)), b"x")]))
        oks = 0
        for _ in range(n):
            _, op, _ = raw.recv()
            oks += op == P.Op.OK
        raw.close()
        assert oks == n

    def test_garbage_bytes_get_protocol_error(self, tmp_path, servers):
        server = start_server(servers, "posix", tmp_path)
        sock = socket.create_connection(server.addr, timeout=10)
        sock.sendall(b"GET / HTTP/1.1\r\n\r\n" + b"\x00" * 64)
        # server answers with an ERR frame (or closes) instead of hanging
        data = sock.recv(1 << 16)
        sock.close()
        if data:
            _, op, cur = P.split_frame(data[4:])
            assert op == P.Op.ERR


def _hello_frame() -> bytes:
    return P.encode_frame(0, P.Op.HELLO, P.encode_hello())


def _trickle(sock, data: bytes, sizes) -> None:
    """Send ``data`` in pieces of the sizes given (the last one repeated),
    each flushed on its own, so the server sees them in separate reads."""
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    at, i = 0, 0
    while at < len(data):
        n = sizes[min(i, len(sizes) - 1)]
        sock.sendall(data[at:at + n])
        at += n
        i += 1
        time.sleep(0.0005)


def _recv_frame(sock):
    buf = b""
    while len(buf) < 4:
        chunk = sock.recv(4 - len(buf))
        if not chunk:
            return None
        buf += chunk
    n = P.frame_length(buf)
    body = b""
    while len(body) < n:
        body += sock.recv(n - len(body))
    return P.split_frame(body)


class TestFrameIntake:
    """The server reads each frame into one buffer sized from its header;
    whatever the reads' sizes, frames come out whole and in order."""

    @pytest.mark.parametrize("sizes", [
        [1],
        [3, 5, 1, 2, 7, 11, 4093, 13, 1 << 16],
        # a whole frame and the first 3 bytes of the next one's header
        [len(_hello_frame()) + 3, 1 << 16],
    ], ids=["1-byte", "odd", "straddling"])
    def test_frames_fed_in_pieces_are_answered(self, tmp_path, servers, sizes):
        server = start_server(servers, "posix", tmp_path)
        items = [(ident(step=str(s), param=p), bytes(range(256)) * (s + 1))
                 for s in range(2) for p in ("2t", "10u")]
        stream = (_hello_frame()
                  + P.encode_frame(5, P.Op.ARCHIVE_BATCH, P.encode_archive_batch(items))
                  + P.encode_frame(6, P.Op.FLUSH))
        sock = socket.create_connection(server.addr, timeout=30)
        try:
            _trickle(sock, stream, sizes)
            assert [_recv_frame(sock)[:2] for _ in range(3)] == [
                (0, P.Op.OK), (5, P.Op.OK), (6, P.Op.OK)]
        finally:
            sock.close()
        with connect(server) as fdb:
            for key, data in items:
                assert fdb.read(key) == data
        assert server.wire_stats.snapshot()["ops"]["wire_frame_read"] >= 3

    def test_frame_larger_than_socket_buffers_round_trips(self, tmp_path, servers):
        server = start_server(servers, "daos", tmp_path)
        data = os.urandom(48 << 20)
        with connect(server, pool_size=1) as fdb:
            fdb.archive(ident(), data)
            fdb.flush()
            assert fdb.read(ident()) == data
        snap = server.wire_stats.snapshot()
        assert snap["op_bytes_r"]["wire_frame_read"] > len(data)

    def test_oversized_length_is_refused_without_allocating(self, tmp_path, servers):
        server = start_server(servers, "posix", tmp_path)
        raw = _RawClient(server.addr)
        tracemalloc.start()
        try:
            raw.sock.sendall((0xFFFFFFFF).to_bytes(4, "big"))
            _, op, cur = raw.recv()
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
            raw.close()
        assert op == P.Op.ERR
        err = P.decode_error(cur)
        assert err.remote_type == "ProtocolError" and "exceeds" in str(err)
        assert peak < 16 << 20
        assert server.wire_stats.snapshot()["ops"]["wire_conn_error"] == 1

    @pytest.mark.parametrize("hello", [False, True], ids=["before-hello", "after-hello"])
    def test_a_header_alone_reserves_little(self, tmp_path, servers, hello):
        """A header that promises a body just under ``max_frame`` and sends
        100 bytes of it holds the server to a buffer that grows with the
        bytes received, not one of the promised size."""
        server = start_server(servers, "posix", tmp_path)
        sock = socket.create_connection(server.addr, timeout=30)
        if hello:
            sock.sendall(_hello_frame())
            assert _recv_frame(sock)[:2] == (0, P.Op.OK)
        n = P.DEFAULT_MAX_FRAME - 1
        tracemalloc.start()
        try:
            sock.sendall(n.to_bytes(4, "big") + b"\0" * 100)
            sock.shutdown(socket.SHUT_WR)
            req_id, op, cur = _recv_frame(sock)
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
            sock.close()
        assert (req_id, op) == (0, P.Op.ERR)
        assert f"(100/{n} bytes)" in str(P.decode_error(cur))
        assert peak < 40 << 20

    def test_frame_counters_count_frames_and_reads_apart(self, tmp_path, servers):
        server = start_server(servers, "posix", tmp_path)
        sock = socket.create_connection(server.addr, timeout=30)
        try:
            frame = P.encode_frame(7, P.Op.FLUSH)
            _trickle(sock, _hello_frame() + frame, [len(_hello_frame()), 1])
            assert [_recv_frame(sock)[:2] for _ in range(2)] == [(0, P.Op.OK), (7, P.Op.OK)]
        finally:
            sock.close()
        snap = server.wire_stats.snapshot()
        assert snap["ops"]["wire_frame_read"] == 2
        # one read or more a frame, and no more than the flush's body bytes
        # (sent a byte at a time) and the hello's one
        assert 2 <= snap["counters"]["wire_frame_read_calls"] <= 1 + len(frame) - 4
        assert snap["op_bytes_r"]["wire_frame_read"] == len(_hello_frame()) + len(frame) - 8

    @pytest.mark.parametrize("cut, what", [(2, "mid frame header"), (4 + 100, "(100/")],
                             ids=["header", "body"])
    def test_eof_inside_a_frame_is_a_clean_error(self, tmp_path, servers, cut, what):
        server = start_server(servers, "posix", tmp_path)
        raw = _RawClient(server.addr)
        frame = P.encode_frame(3, P.Op.ARCHIVE_BATCH,
                               P.encode_archive_batch([(ident(), b"z" * 1000)]))
        raw.sock.sendall(frame[:cut])
        raw.sock.shutdown(socket.SHUT_WR)
        req_id, op, cur = raw.recv()
        raw.close()
        assert (req_id, op) == (0, P.Op.ERR)
        err = P.decode_error(cur)
        assert err.remote_type == "ProtocolError" and what in str(err)
        assert server.wire_stats.snapshot()["ops"]["wire_conn_error"] == 1
        with connect(server) as fdb:  # the server serves on
            fdb.flush()

    def test_pipelined_frames_past_the_bound_pause_reading(self, tmp_path, servers):
        server = start_server(servers, "posix", tmp_path, max_inflight=2)
        real_list = server.fdb.list
        server.fdb.list = lambda req: (time.sleep(0.01), real_list(req))[1]
        raw = _RawClient(server.addr)
        n = 24
        for i in range(n):
            raw.send(100 + i, P.Op.LIST, P.encode_request(Request({"step": str(i)})))
        got = [raw.recv()[:2] for _ in range(n)]
        raw.close()
        del server.fdb.list
        assert got == [(100 + i, P.Op.OK) for i in range(n)]
        assert server.wire_stats.snapshot()["ops"]["wire_read_paused"] >= 1


# ---------------------------------------------------------------------------
# Equivalence: SelectFDB with one remote tier == bare backend
# ---------------------------------------------------------------------------

class TestRemoteRoutingEquivalence(test_select.TestRoutingEquivalence):
    """The existing single-rule equivalence suite, with the routed side's
    tier moved BEHIND the wire: SelectFDB -> RemoteFDB -> server -> backend
    must stay observationally identical to the bare backend."""

    @pytest.fixture(autouse=True)
    def _track_servers(self):
        self._servers: list[FDBServer] = []
        yield
        for s in self._servers:
            s.stop()

    def _pair(self, backend, tmp_path):
        bare = make_bare(backend, tmp_path, "bare")
        server = FDBServer(make_bare(backend, tmp_path, "routed"), owns_fdb=True)
        server.start()
        self._servers.append(server)
        host, port = server.addr
        routed = SelectFDB([("class=od", RemoteFDB(f"{host}:{port}"))])
        return bare, routed


# ---------------------------------------------------------------------------
# Declarative config + composition
# ---------------------------------------------------------------------------

class TestRemoteConfig:
    def test_inner_form_builds_self_hosted_tree(self, tmp_path):
        cfg = {"type": "remote",
               "inner": {"backend": "posix", "root": str(tmp_path / "r")}}
        FDBConfig(cfg)  # validates + JSON round-trips
        assert FDBConfig.from_json(FDBConfig(cfg).to_json()) == cfg
        with build_fdb(cfg) as fdb:
            assert isinstance(fdb, RemoteFDB)
            fdb.archive(ident(), b"x")
            fdb.flush()
            assert fdb.read(ident()) == b"x"

    def test_addr_form_connects_to_running_server(self, tmp_path, servers):
        server = start_server(servers, "daos", tmp_path)
        host, port = server.addr
        with build_fdb({"type": "remote", "addr": f"{host}:{port}",
                        "pool_size": 1, "retries": 1}) as fdb:
            fdb.archive(ident(), b"via-config")
            fdb.flush()
            assert fdb.read(ident()) == b"via-config"

    def test_validation_rejects_malformed_nodes(self):
        from repro.core import ConfigError
        from repro.core.config import validate_config

        with pytest.raises(ConfigError, match="exactly one"):
            validate_config({"type": "remote"})
        with pytest.raises(ConfigError, match="exactly one"):
            validate_config({"type": "remote", "addr": "h:1",
                            "inner": {"backend": "posix", "root": "/x"}})
        with pytest.raises(ConfigError, match="pool_size"):
            validate_config({"type": "remote", "addr": "h:1", "pool_size": "big"})

    def test_async_over_remote_composes(self, tmp_path):
        cfg = {"type": "async", "writers": 2,
               "inner": {"type": "remote",
                         "inner": {"backend": "posix", "root": str(tmp_path / "a")}}}
        with build_fdb(cfg) as fdb:
            assert isinstance(fdb, AsyncFDB)
            items = [(ident(step=str(s), param=p), f"{s}{p}".encode())
                     for s in range(3) for p in ("2t", "10u")]
            for k, v in items:
                fdb.archive(k, v)
            fdb.flush()
            for k, v in items:
                assert fdb.read(k) == v

    def test_serve_fdb_convenience_and_bad_addr(self, tmp_path):
        server = serve_fdb(make_bare("posix", tmp_path, "sv"))
        try:
            assert server.addr is not None
        finally:
            server.stop()
        with pytest.raises(ValueError, match="host:port"):
            RemoteFDB("not-an-address")


# ---------------------------------------------------------------------------
# Satellite regression: FieldSet fetch-contract validation
# ---------------------------------------------------------------------------

class TestFieldResolution:
    KEYS = [ident(step=str(s)) for s in range(4)]

    def test_short_fetch_raises_naming_keys(self):
        fs = FieldSet(self.KEYS, lambda ks: [None] * (len(ks) - 1),
                      batch_size=None)
        with pytest.raises(FieldResolutionError, match="step=0") as ei:
            fs.handles()
        assert ei.value.expected == 4 and ei.value.got == 3
        assert "4 requested keys" in str(ei.value)

    def test_long_fetch_also_rejected(self):
        fs = FieldSet(self.KEYS, lambda ks: [None] * (len(ks) + 2),
                      batch_size=None)
        with pytest.raises(FieldResolutionError):
            fs.handles()

    def test_chunked_path_validates_too(self):
        fs = FieldSet(self.KEYS, lambda ks: [], batch_size=2)
        with pytest.raises(FieldResolutionError, match="fetch returned 0"):
            fs[self.KEYS[0]]

    def test_key_list_is_truncated_in_message(self):
        keys = [ident(step=str(s)) for s in range(10)]
        fs = FieldSet(keys, lambda ks: [], batch_size=None)
        with pytest.raises(FieldResolutionError, match="5 more"):
            fs.handles()

    def test_correct_fetch_with_absent_fields_still_fine(self):
        fs = FieldSet(self.KEYS, lambda ks: [None] * len(ks), batch_size=2)
        assert fs.handles() == [None] * 4
        assert fs.missing() == self.KEYS


# ---------------------------------------------------------------------------
# Streamed replies: a read past the part bound comes in parts
# ---------------------------------------------------------------------------

#: the benchmark's tiers: 16-bit codes over DAOS, 24-bit over POSIX
STREAM_TIERS = [("daos", 16), ("posix", 24)]
#: a max_frame that holds one to three fields of a 16x128 grid
SMALL_FRAME = 16 << 10


def _column(fdb, nbits: int, n: int = 24) -> dict:
    """``n`` fields' worth of codec-sized payloads of one tier, archived
    one a frame: the key->bytes reference."""
    from repro.core import wire_size

    rng = np.random.default_rng(nbits)
    ref = {ident(step=str(s)): rng.bytes(wire_size((16, 128), nbits)) for s in range(n)}
    for k, p in ref.items():
        fdb.archive_batch([(k, p)])
    fdb.flush()
    return ref


def _parts(opcode, ref: dict, bound: int) -> int:
    return len(P.split_reply(opcode, [(k, len(p)) for k, p in ref.items()], bound))


def _frame_op(frame) -> int:
    """The opcode of a frame the server writes: ``bytes``, or a list of
    buffers whose first is the length prefix and header."""
    return bytes(frame[0] if isinstance(frame, list) else frame[:9])[8]


def _served(server, op: str, n: int) -> int:
    """The server's count of ``op``, once it has reached ``n`` (an op is
    counted after its reply)."""
    deadline = time.time() + 10
    while server.wire_stats.snapshot()["ops"].get(op, 0) < n and time.time() < deadline:
        time.sleep(0.01)
    return server.wire_stats.snapshot()["ops"].get(op, 0)


@pytest.mark.parametrize("backend,nbits", STREAM_TIERS)
class TestStreamedReplies:
    def _pair(self, servers, backend, tmp_path, **kw):
        server = start_server(servers, backend, tmp_path, max_frame=SMALL_FRAME)
        return server, connect(server, max_frame=SMALL_FRAME, **kw)

    def test_streamed_reads_match_the_reference_and_the_tree(
        self, backend, nbits, tmp_path, servers
    ):
        server, fdb = self._pair(servers, backend, tmp_path)
        with fdb:
            ref = _column(fdb, nbits)
            keys = list(ref)
            got = [h.read() for h in fdb.retrieve_batch(keys)]
            assert got == [ref[k] for k in keys] == [server.fdb.read(k) for k in keys]
            request = {**dict(keys[0]), "step": [k["step"] for k in keys]}
            assert fdb.retrieve_many(request).read_all() == ref
        bound = P.part_bound(SMALL_FRAME)
        batch, many = _parts(P.Op.RETRIEVE_BATCH, ref, bound), _parts(P.Op.RETRIEVE_MANY, ref, bound)
        assert batch >= 8 and many >= 8
        snap = server.wire_stats.snapshot()
        assert snap["ops"]["wire_part"] == batch + many
        # the server holds one part at a time: never more than two
        assert 0 < snap["counters"]["wire_part_buffered_max"] <= 2 * (SMALL_FRAME + 4)

    def test_parts_read_as_views_and_a_batch_as_bytes(self, backend, nbits, tmp_path, servers):
        server, fdb = self._pair(servers, backend, tmp_path)
        with fdb:
            ref = _column(fdb, nbits)
            keys = list(ref)
            parts = list(fdb.retrieve_batch_parts(keys))
            assert len(parts) >= 8
            views = [h.read() for _, handles, _ in parts for h in handles]
            assert all(type(v) is memoryview for v in views)
            assert views == [ref[k] for k in keys]
            assert all(type(h.read()) is bytes for h in fdb.retrieve_batch(keys))

    def test_parts_are_handed_on_as_they_land(self, backend, nbits, tmp_path, servers):
        server, fdb = self._pair(servers, backend, tmp_path)
        with fdb:
            ref = _column(fdb, nbits)
            keys = list(ref)[::-1]
            parts = list(fdb.retrieve_batch_parts(keys))
            assert len(parts) == _parts(P.Op.RETRIEVE_BATCH, ref, P.part_bound(SMALL_FRAME))
            assert [p for pos, _, _ in parts for p in pos] == list(range(len(keys)))
            assert [h.read() for _, hs, _ in parts for h in hs] == [ref[k] for k in keys]
            # every part says the reply goes on: its END frame is still to come
            assert all(more for _, _, more in parts)

    def test_err_partway_raises_remote_error(
        self, backend, nbits, tmp_path, servers, monkeypatch
    ):
        import repro.core.remote.server as server_mod

        server, fdb = self._pair(servers, backend, tmp_path)
        with fdb:
            ref = _column(fdb, nbits)
            calls = []
            read_items = server_mod._read_items

            def failing_second_part(handles):
                calls.append(len(handles))
                if len(calls) == 2:
                    raise RuntimeError("store failed mid reply")
                return read_items(handles)

            monkeypatch.setattr(server_mod, "_read_items", failing_second_part)
            with pytest.raises(RemoteError, match="store failed mid reply"):
                fdb.retrieve_batch(list(ref))
            assert fdb.wire_stats.snapshot()["ops"].get("remote_retry", 0) == 0
            # the run ended cleanly: the connection serves the next request
            k = next(iter(ref))
            assert fdb.read(k) == ref[k]

    def test_truncated_run_raises_protocol_error(
        self, backend, nbits, tmp_path, servers, monkeypatch
    ):
        import repro.core.remote.server as server_mod

        server, fdb = self._pair(servers, backend, tmp_path)
        with fdb:
            ref = _column(fdb, nbits)
            send_frame = server_mod._Connection.send_frame

            async def cut_after_first_part(self, frame):
                await send_frame(self, frame)
                if _frame_op(frame) == P.Op.PART:
                    self.transport.close()

            monkeypatch.setattr(server_mod._Connection, "send_frame", cut_after_first_part)
            with pytest.raises(ProtocolError, match="closed the connection"):
                fdb.retrieve_batch(list(ref))
            # past the first part, a fault is not retried
            assert fdb.wire_stats.snapshot()["ops"].get("remote_retry", 0) == 0
            assert _served(server, "wire_retrieve_batch", 1) == 1

    def test_a_fault_before_the_first_part_is_retried(
        self, backend, nbits, tmp_path, servers, monkeypatch
    ):
        import repro.core.remote.server as server_mod

        server, fdb = self._pair(servers, backend, tmp_path)
        with fdb:
            ref = _column(fdb, nbits)
            send_frame = server_mod._Connection.send_frame
            cut = []

            async def cut_before_first_part(self, frame):
                if _frame_op(frame) == P.Op.PART and not cut:
                    cut.append(self.name)
                    self.transport.close()
                    raise ConnectionResetError("cut before the first part")
                await send_frame(self, frame)

            monkeypatch.setattr(server_mod._Connection, "send_frame", cut_before_first_part)
            got = [h.read() for h in fdb.retrieve_batch(list(ref))]
            assert got == list(ref.values())
            assert fdb.wire_stats.snapshot()["ops"]["remote_retry"] == 1

    def test_a_frame_past_max_frame_is_not_retried(self, backend, nbits, tmp_path, servers):
        server = start_server(servers, backend, tmp_path)
        with connect(server) as writer:
            ref = _column(writer, nbits, n=2)
        # a client whose max_frame is below one field: the reply is one
        # frame it refuses, and sending the request again would only
        # rebuild that frame
        with connect(server, max_frame=4096, retries=2) as fdb:
            with pytest.raises(P.FrameTooLarge, match="exceeds"):
                fdb.retrieve_batch(list(ref))
            assert fdb.wire_stats.snapshot()["ops"].get("remote_retry", 0) == 0
        assert _served(server, "wire_retrieve_batch", 1) == 1


class TestStreamNegotiation:
    def _served_column(self, servers, tmp_path):
        server = start_server(servers, "daos", tmp_path)
        with connect(server) as fdb:
            ref = _column(fdb, 16, n=6)
        return server, ref

    def _raw_frames(self, server, keys, hello: bytes) -> list[bytes]:
        """Every frame of the reply to one RETRIEVE_BATCH, raw, up to the
        one that ends it."""
        sock = socket.create_connection(server.addr, timeout=30)
        try:
            sock.sendall(P.encode_frame(0, P.Op.HELLO, hello))
            assert _recv_frame(sock)[1] == P.Op.OK
            sock.sendall(P.encode_frame(5, P.Op.RETRIEVE_BATCH, P.encode_keys(keys)))
            frames = []
            while True:
                hdr = b""
                while len(hdr) < 4:
                    hdr += sock.recv(4 - len(hdr))
                body = b""
                while len(body) < P.frame_length(hdr):
                    body += sock.recv(P.frame_length(hdr) - len(body))
                frames.append(hdr + body)
                if body[4] != P.Op.PART:
                    return frames
        finally:
            sock.close()

    def test_a_reply_under_the_bound_is_todays_single_frame(self, servers, tmp_path):
        server, ref = self._served_column(servers, tmp_path)
        frames = self._raw_frames(server, list(ref), P.encode_hello())
        assert frames == [P.encode_frame(5, P.Op.OK, P.encode_handles(list(ref.values())))]

    def test_only_a_peer_that_negotiated_streaming_gets_parts(
        self, servers, tmp_path, monkeypatch
    ):
        server, ref = self._served_column(servers, tmp_path)
        monkeypatch.setattr(P, "PART_BYTES", 3 * len(next(iter(ref.values()))))
        # a level-2 peer (tracing, no streaming) gets the whole reply as one
        # frame, as before
        old = self._raw_frames(server, list(ref), P.encode_hello(P.TRACE_EXT_VERSION))
        assert old == [P.encode_frame(5, P.Op.OK, P.encode_handles(list(ref.values())))]
        new = self._raw_frames(server, list(ref), P.encode_hello())
        ops = [f[8] for f in new]
        assert ops == [P.Op.PART] * 3 + [P.Op.END]
        payloads = [p for f in new[:-1] for p in P.decode_handles(P.split_frame(f[4:])[2])]
        assert payloads == list(ref.values())
        assert P.decode_end(P.split_frame(new[-1][4:])[2]) == (3, len(ref))

    def test_the_benchmark_replies_stay_single_frames(self):
        """The largest reply of the 8-level cells (8 cold-tier fields of the
        0.1-degree grid, 207.5 MB) is one frame; a 137-level column is
        parts, each within the bound."""
        from repro.core import wire_size

        key = Key({"class": "od", "stream": "enfo", "expver": "0001", "date": "20240601",
                   "time": "0000", "type": "pf", "levtype": "ml", "number": "1",
                   "step": "0", "param": "t", "levelist": "137"})
        cold, hot = wire_size((1801, 3600), 24), wire_size((1801, 3600), 16)
        assert 8 * cold == 207_475_456
        bound = P.part_bound()
        for op in (P.Op.RETRIEVE_BATCH, P.Op.RETRIEVE_MANY):
            assert len(P.split_reply(op, [(key, cold)] * 8, bound)) == 1
            assert len(P.split_reply(op, [(key, cold)] * 137, bound)) == 14
            assert len(P.split_reply(op, [(key, hot)] * 137, bound)) == 7
        assert P.part_bound() == P.PART_BYTES < P.DEFAULT_MAX_FRAME
        assert P.part_bound(1 << 20) == 1 << 20
