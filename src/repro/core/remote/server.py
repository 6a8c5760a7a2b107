"""The asyncio FDB server — any ``build_fdb`` tree behind a TCP endpoint.

This is the paper's deployment shape: the catalogue/store services run on
storage nodes, clients on compute nodes talk to them over a network (§1.2).
The server fronts ANY :class:`~repro.core.client.FDBClient` — a bare
backend, a tiered SelectFDB, a router — so the whole composition grammar is
servable with one line::

    server = FDBServer({"backend": "posix", "root": "/data/fdb"})
    host, port = server.start()

or from a shell (blocks until interrupted)::

    python -m repro.core.remote.server --config fdb.json --port 7511

Concurrency model:

- one event-loop thread serves every connection.  Each connection's intake
  is an :class:`asyncio.BufferedProtocol`.  Between frames it reads into a
  64 KiB head buffer, so a request frame arrives with its header in one
  ``recv_into``; a larger frame gets one ``bytearray`` for its body (after
  the ``max_frame`` check), and its body goes straight into it: one
  ``recv_into`` takes everything the kernel holds for the frame, so a large
  frame costs a few loop turns, not one per 256 KiB, and is never copied to
  peel its header.  The buffer is allocated at most 16 MiB ahead of the
  bytes received and doubles as it fills, so a header alone reserves no
  more than that;
- complete frames go onto a per-connection queue; when it holds
  ``max_inflight`` frames (and whatever other frames the same read
  completed) the intake pauses the transport and TCP flow control pushes
  back on the client — per-connection backpressure, not unbounded
  buffering — and taking a frame off the queue resumes it;
- one worker coroutine per connection executes ops serially (a client's
  ``archive`` -> ``flush`` ordering survives the wire) and hands the
  blocking FDB calls to a thread pool, so connections run concurrently and
  contention lands on the backend's own locks, exactly where the paper
  puts it.  It is the connection's only writer: replies go out through
  ``transport.write`` and wait for the transport's write buffer to drain;
- a read's reply past the part bound goes, to a peer that negotiated
  :data:`~repro.core.remote.protocol.STREAM_EXT_VERSION`, as ``PART``
  frames of whole items closed by ``END``: the executor thread reads one
  part's payloads from the store, frames it, hands it to the loop and waits
  until the transport's write buffer has drained before it reads the next,
  so a served read holds about one part, not the whole reply;
- wire-level request batching: consecutive queued ``ARCHIVE_BATCH`` frames
  are coalesced into ONE backend ``archive_batch`` call (each frame still
  gets its own response), so a bursty client amortises backend rounds the
  same way :class:`~repro.core.async_fdb.AsyncFDB` writers do locally.

Per-connection wire telemetry (bytes in/out, handling time, coalesced frame
counts, per-connection op shards) accumulates in ``wire_stats`` — an
:class:`~repro.metrics.iostats.IOStats` like every other sink in the repo.
``wire_frame_read`` counts the frames read, their body bytes and the seconds
from each header to its complete body, and ``counters["wire_frame_read_calls"]``
the ``recv_into`` calls that filled those bodies; a traced op's ``server.*``
span carries the same for its frame as ``read_s`` and ``read_calls``, beside
``queued_s``.  A streamed reply records each part as ``wire_part`` (its
frame bytes, and the seconds from its first store read to its write
drained) and the largest part held in ``counters["wire_part_buffered_max"]``;
its traced ``server.<op>`` span holds one ``server.<op>.part`` span a part,
with the part's ``bytes`` and ``fields``, and carries ``parts`` and
``buffered_max``.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Mapping, NamedTuple

from ...metrics.iostats import IOStats
from ...obs.tracer import NULL_TRACER, SpanContext, Tracer, install_tracer
from . import protocol as P
from .protocol import Cursor, Op, ProtocolError

__all__ = ["FDBServer", "serve_fdb"]

#: sentinel the intake enqueues on clean EOF so the worker drains and exits
_EOF = object()

#: bytes a connection reads between frames: a request frame arrives with
#: its header in one ``recv_into``, and a larger frame's first bytes move
#: from here into the buffer sized from its header
_HEAD_BYTES = 1 << 16

#: the most a frame's body buffer is allocated ahead of the bytes received
#: (it doubles as it fills), so a header that promises a large body and
#: sends none holds the server to this much
_AHEAD_BYTES = 16 << 20

#: span names per served op (precomputed — no per-op string building)
_SERVER_SPANS = {
    Op.RETRIEVE_BATCH: "server.retrieve_batch",
    Op.RETRIEVE_MANY: "server.retrieve_many",
    Op.LIST: "server.list",
    Op.WIPE: "server.wipe",
    Op.FLUSH: "server.flush",
    Op.STATS: "server.stats",
}

#: span names of the parts of a streamed reply
_PART_SPANS = {op: _SERVER_SPANS[op] + ".part" for op in (Op.RETRIEVE_BATCH, Op.RETRIEVE_MANY)}


class _Frame(NamedTuple):
    """One frame body off the wire and how it was read."""

    body: bytearray
    #: when the body was complete (``time.perf_counter``)
    t_read: float
    #: seconds from the complete header to the complete body
    read_s: float
    #: ``recv_into`` calls that filled the body
    read_calls: int


class _Connection(asyncio.BufferedProtocol):
    """One client connection: the frame intake, the bounded queue of
    complete frames, and the reply path (module docstring).  The queue
    holds :class:`_Frame` items, then :data:`_EOF` or the error that ended
    the intake."""

    def __init__(self, server: "FDBServer", name: str):
        self.name = name
        self._server = server
        self._head = bytearray(_HEAD_BYTES)
        self._body: bytearray | None = None  # the frame body being filled
        self._need = 0  # its length, from its header
        self._got = 0  # bytes in the head, or in the body while there is one
        self._calls = 0
        self._t_hdr = 0.0
        self._frames: asyncio.Queue = asyncio.Queue()
        self._paused = False  # reading paused by a full queue
        self._ended = False  # no more frames: EOF, a bad frame, a lost socket
        self._lost = False
        self._drained: asyncio.Future | None = None  # set while writing is paused
        self.transport: asyncio.Transport | None = None
        #: the HELLO extension level both ends speak (1 until the handshake)
        self.ext = 1

    # ------------------------------------------------------------- intake
    def connection_made(self, transport) -> None:
        self.transport = transport
        self._server._serve(self)

    def get_buffer(self, sizehint: int) -> memoryview:
        body = self._body
        if body is None:
            return memoryview(self._head)[self._got:]
        if self._got == len(body):
            # full, and the frame is not: double it, up to the frame's length
            body += bytes(min(self._need - self._got, max(self._got, _AHEAD_BYTES)))
        return memoryview(body)[self._got:]

    def buffer_updated(self, nbytes: int) -> None:
        self._got += nbytes
        if self._body is None:
            self._split_head()
            return
        self._calls += 1
        if self._got == self._need:
            self._push(self._body, self._t_hdr, self._calls)
            self._body = None
            self._got = 0

    def _split_head(self) -> None:
        """Queue every frame complete in the head buffer.  A frame that is
        not gets a body buffer (after the ``max_frame`` check), its bytes so
        far are moved there, and the next ``recv_into`` goes on from them;
        fewer than 4 bytes left over stay at the head's start."""
        head, end, at = self._head, self._got, 0
        t = time.perf_counter()
        while end - at >= 4:
            try:
                n = P.frame_length(head[at:at + 4], max_frame=self._server._max_frame)
            except ProtocolError as e:
                self.transport.pause_reading()
                self._end(e)
                return
            have = end - at - 4
            if have >= n:
                self._push(head[at + 4:at + 4 + n], t, 1)
                at += 4 + n
                continue
            body = bytearray(min(n, have + _AHEAD_BYTES))
            body[:have] = head[at + 4:end]
            self._body, self._need, self._got = body, n, have
            self._t_hdr, self._calls = t, int(have > 0)
            return
        head[:end - at] = head[at:end]
        self._got = end - at

    def _push(self, body: bytearray, t_hdr: float, calls: int) -> None:
        t = time.perf_counter()
        frame = _Frame(body, t, t - t_hdr, calls)
        stats = self._server.wire_stats
        with stats.lock:
            stats.record(
                "wire_frame_read", seconds=frame.read_s, nbytes_r=len(body), shard=self.name
            )
            stats.counters["wire_frame_read_calls"] += calls
        self._frames.put_nowait(frame)
        if self._frames.qsize() >= self._server._max_inflight and not self._paused:
            # backpressure: leave the rest in the socket until the worker
            # takes a frame, so TCP flow control holds the client back
            self._paused = True
            self.transport.pause_reading()
            self._server.wire_stats.record("wire_read_paused", shard=self.name)

    def eof_received(self) -> bool:
        self._end(self._cut_short() or _EOF)
        return True  # keep the transport open: queued ops still get replies

    def connection_lost(self, exc: Exception | None) -> None:
        self._lost = True
        self._end(self._cut_short(exc) or _EOF)
        if self._drained is not None and not self._drained.done():
            self._drained.set_result(None)

    def _cut_short(self, exc: Exception | None = None) -> Exception | None:
        """The error of a stream that ended inside a frame, else None."""
        if self._body is not None:
            return exc or ProtocolError(
                f"connection closed mid frame ({self._got}/{self._need} bytes)"
            )
        if self._got:
            return exc or ProtocolError("connection closed mid frame header")
        return None

    def _end(self, item) -> None:
        if not self._ended:
            self._ended = True
            self._frames.put_nowait(item)

    async def next_frame(self):
        """The next queued item, waiting for one."""
        item = await self._frames.get()
        self._took()
        return item

    def next_frame_nowait(self):
        """The next queued item; :class:`asyncio.QueueEmpty` if none."""
        item = self._frames.get_nowait()
        self._took()
        return item

    def _took(self) -> None:
        if self._paused and self._frames.qsize() < self._server._max_inflight:
            self._paused = False
            if not self._ended:
                self.transport.resume_reading()

    # -------------------------------------------------------------- replies
    def pause_writing(self) -> None:
        self._drained = asyncio.get_running_loop().create_future()

    def resume_writing(self) -> None:
        if self._drained is not None and not self._drained.done():
            self._drained.set_result(None)
        self._drained = None

    async def send(self, req_id: int, opcode: int, payload: bytes | list) -> None:
        """Send a reply whose payload is ``bytes``, or a list of buffers
        (a read's reply, its field payloads among them) written as they are."""
        if isinstance(payload, list):
            await self.send_frame([P.frame_head(req_id, opcode, P.pieces_len(payload)), *payload])
        else:
            await self.send_frame(P.encode_frame(req_id, opcode, payload))

    async def send_frame(self, frame: bytes | list) -> None:
        """Write one reply frame (``bytes``, or a list of buffers the
        transport sends without joining), then wait while the transport's
        write buffer is above its high-water mark.  One coroutine of the
        connection writes at a time, so one waiter is enough."""
        if self._lost:
            raise ConnectionResetError("connection lost")
        if isinstance(frame, list):
            self.transport.writelines(frame)
        else:
            self.transport.write(frame)
        if self._drained is not None:
            await self._drained
        if self._lost:
            raise ConnectionResetError("connection lost")

    def close(self) -> None:
        self.transport.close()


class FDBServer:
    """Serve one FDB tree on a TCP address from a background thread.

    ``fdb`` is a live :class:`~repro.core.client.FDBClient` (caller-owned) or
    a config mapping (:func:`~repro.core.config.build_fdb` grammar — the
    server builds AND owns the tree, closing it on :meth:`stop`).
    ``port=0`` binds an ephemeral port; :meth:`start` returns the bound
    ``(host, port)``.
    """

    def __init__(
        self,
        fdb,
        *,
        host: str = "127.0.0.1",
        port: int = 0,
        max_inflight: int = 32,
        coalesce: int = 16,
        max_frame: int = P.DEFAULT_MAX_FRAME,
        owns_fdb: bool | None = None,
    ):
        if isinstance(fdb, Mapping):
            from ..config import build_fdb

            fdb = build_fdb(fdb)
            owns_fdb = True if owns_fdb is None else owns_fdb
        self.fdb = fdb
        self._owns_fdb = bool(owns_fdb)
        self._host = host
        self._port = port
        if max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        self._max_inflight = max_inflight
        self._coalesce = max(1, coalesce)
        self._max_frame = max_frame
        self.addr: tuple[str, int] | None = None
        self.wire_stats = IOStats("remote-server")
        #: server-side tracer: the null tracer until the first TRACED frame
        #: (or TRACE round) arrives — an untraced client pays nothing, a
        #: traced one gets server-side spans stitched to its trace ids and
        #: returned over the Op.TRACE round
        self.tracer = NULL_TRACER
        self._tracer_mu = threading.Lock()
        self._conn_ids = itertools.count()
        self._conn_tasks: set[asyncio.Task] = set()
        self._executor = ThreadPoolExecutor(
            max_workers=max(4, max_inflight), thread_name_prefix="fdb-serve"
        )
        self._thread: threading.Thread | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._stop_ev: asyncio.Event | None = None
        self._started = threading.Event()
        self._start_exc: BaseException | None = None
        self._stopped = False

    # ------------------------------------------------------------- lifecycle
    def start(self) -> tuple[str, int]:
        """Run the server on a background thread; returns the bound addr."""
        if self._thread is not None:
            raise RuntimeError("server already started")
        self._thread = threading.Thread(
            target=self._run, name="fdb-server", daemon=True
        )
        self._thread.start()
        self._started.wait(30)
        if self._start_exc is not None:
            raise self._start_exc
        if self.addr is None:
            raise RuntimeError("server failed to start within 30s")
        return self.addr

    def stop(self) -> None:
        """Stop serving: close the listener and every open connection, then
        close the FDB tree if this server owns it.  Idempotent."""
        if self._stopped:
            return
        self._stopped = True
        if self._loop is not None and self._stop_ev is not None:
            self._loop.call_soon_threadsafe(self._stop_ev.set)
        if self._thread is not None:
            self._thread.join(timeout=30)
        self._executor.shutdown(wait=False, cancel_futures=True)
        if self._owns_fdb:
            self.fdb.close()

    def __enter__(self) -> "FDBServer":
        if self._thread is None:
            self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # ----------------------------------------------------------- event loop
    def _run(self) -> None:
        try:
            asyncio.run(self._main())
        except BaseException as e:  # noqa: BLE001 — surfaced by start()
            if not self._started.is_set():
                self._start_exc = e
                self._started.set()

    async def _main(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._stop_ev = asyncio.Event()
        server = await self._loop.create_server(
            lambda: _Connection(self, f"conn{next(self._conn_ids)}"), self._host, self._port
        )
        sock = server.sockets[0].getsockname()
        self.addr = (sock[0], sock[1])
        self._started.set()
        try:
            await self._stop_ev.wait()
        finally:
            server.close()
            # close every connection first: wait_closed waits until every
            # one has been dropped
            for t in list(self._conn_tasks):
                t.cancel()
            await asyncio.gather(*self._conn_tasks, return_exceptions=True)
            await server.wait_closed()

    # ----------------------------------------------------------- connections
    def _serve(self, conn: _Connection) -> None:
        task = asyncio.get_running_loop().create_task(self._serve_conn(conn))
        self._conn_tasks.add(task)
        task.add_done_callback(self._conn_tasks.discard)

    async def _serve_conn(self, conn: _Connection) -> None:
        try:
            await self._handshake(conn)
            await self._conn_worker(conn)
        except (ProtocolError, ConnectionError, OSError) as e:
            self.wire_stats.record("wire_conn_error", shard=conn.name)
            try:
                await conn.send(0, Op.ERR, P.encode_error(e))
            except (ConnectionError, OSError):
                pass
        finally:
            conn.close()

    def _ensure_tracer(self) -> None:
        """Switch the server-side tracer on (idempotent).  Installs it down
        the whole served tree so backend/tier/codec spans nest under the
        server op spans automatically."""
        if self.tracer.enabled:
            return
        with self._tracer_mu:
            if self.tracer.enabled:
                return
            tracer = Tracer(proc="server")
            install_tracer(self.fdb, tracer)
            self.tracer = tracer

    async def _handshake(self, conn: _Connection) -> None:
        item = await conn.next_frame()
        if item is _EOF:
            raise ConnectionError("peer closed before handshake")
        if isinstance(item, Exception):
            raise item
        req_id, opcode, cur = P.split_frame(item.body)
        if opcode != Op.HELLO:
            raise ProtocolError(
                f"expected HELLO, got opcode {Op.NAMES.get(opcode, opcode)!r}"
            )
        P.decode_hello(cur)
        ext = min(P.decode_hello_ext(cur), P.STREAM_EXT_VERSION)
        from ..config import schema_to_config

        spec = json.dumps(schema_to_config(self.fdb.schema))
        payload = P.pack_str(spec)
        if ext >= P.TRACE_EXT_VERSION:
            # echo the extension level both ends speak as an optional
            # trailing u16 a v1 client never reads — only a peer that
            # advertised it gets it
            payload += P.pack_u16(ext)
            conn.ext = ext
        await conn.send(req_id, Op.OK, payload)
        self.wire_stats.record("wire_hello", shard=conn.name)

    # ---------------------------------------------------------------- worker
    async def _conn_worker(self, conn: _Connection) -> None:
        """Serial op execution for one connection (ordering survives the
        wire), with greedy coalescing of consecutive archive frames; the
        error that ended the intake is raised once the frames before it
        are answered."""
        pending = None
        while True:
            item = pending if pending is not None else await conn.next_frame()
            pending = None
            if item is _EOF:
                return
            if isinstance(item, Exception):
                raise item
            if _base_op(item) == Op.ARCHIVE_BATCH:
                # wire-level batching: drain whatever archive frames are
                # already queued into one backend round (the TRACE_FLAG bit
                # is per-frame — masked off before comparing opcodes)
                frames = [item]
                while len(frames) < self._coalesce:
                    try:
                        nxt = conn.next_frame_nowait()
                    except asyncio.QueueEmpty:
                        break
                    if not isinstance(nxt, _Frame) or _base_op(nxt) != Op.ARCHIVE_BATCH:
                        pending = nxt
                        break
                    frames.append(nxt)
                await self._run_archive_group(frames, conn)
                continue
            try:
                await self._run_op(item, conn)
            except (ConnectionError, OSError):
                return  # peer gone: nothing left to answer

    async def _run_archive_group(self, frames: list[_Frame], conn: _Connection) -> None:
        loop = asyncio.get_running_loop()
        t0 = time.perf_counter()
        try:
            merged = await loop.run_in_executor(
                self._executor, self._archive_frames, frames
            )
            err = None
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — forwarded to the client
            merged, err = 0, e
        dt = time.perf_counter() - t0
        self.wire_stats.record(
            "wire_archive_batch", seconds=dt, shard=conn.name, count=merged or 1,
        )
        if len(frames) > 1:
            self.wire_stats.record("wire_coalesced_frames", count=len(frames), shard=conn.name)
        for f in frames:
            req_id = P.split_frame(f.body)[0]
            if err is None:
                await conn.send(req_id, Op.OK, b"")
            else:
                await conn.send(req_id, Op.ERR, P.encode_error(err))

    def _archive_frames(self, frames: list[_Frame]) -> int:
        """Decode + merge archive frames, one backend ``archive_batch``.
        Runs on the executor — decoding stays off the event loop.  The
        coalesced backend call is ONE server span, parented under the first
        traced frame's wire context (one backend round, one span — exactly
        what the client's wire span timed); its ``queued_s`` runs from the
        first frame's body being read to this thread starting the group,
        and its ``read_s``/``read_calls`` are the first frame's read."""
        t_run = time.perf_counter()
        items = []
        ctx = None
        for f in frames:
            _, opcode, cur = P.split_frame(f.body)
            traced = P.mask_op(opcode)[1]
            if traced:
                tid, sid = P.decode_trace_ctx(cur)
                if ctx is None:
                    self._ensure_tracer()
                    ctx = SpanContext(tid, sid)
            items.extend(P.decode_archive_batch(cur))
        tr = self.tracer
        with tr.span("server.archive_batch", remote_parent=ctx) as sp:
            if tr.enabled:
                first = frames[0]
                sp.set("frames", len(frames))
                sp.set("n_items", len(items))
                sp.set("queued_s", t_run - first.t_read)
                sp.set("read_s", first.read_s)
                sp.set("read_calls", first.read_calls)
            self.fdb.archive_batch(items)
        return len(items)

    async def _run_op(self, frame: _Frame, conn: _Connection) -> None:
        loop = asyncio.get_running_loop()
        req_id, opcode, _ = P.split_frame(frame.body)
        t0 = time.perf_counter()
        try:
            resp_op, payload = await loop.run_in_executor(
                self._executor, self._serve_op, frame, conn
            )
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — forwarded to the client
            payload, resp_op = P.encode_error(e), Op.ERR
        dt = time.perf_counter() - t0
        base = P.mask_op(opcode)[0]
        nbytes = P.pieces_len(payload) if isinstance(payload, list) else len(payload)
        self.wire_stats.record(
            f"wire_{Op.NAMES.get(base, hex(base))}",
            seconds=dt, nbytes_w=nbytes, shard=conn.name,
        )
        await conn.send(req_id, resp_op, payload)

    # --------------------------------------------------------- op execution
    def _serve_op(self, frame: _Frame, conn: _Connection) -> tuple[int, bytes | list]:
        """Decode one request frame, run it against the FDB, and return the
        reply's last frame as ``(opcode, payload)``: ``OK``, or ``END`` after
        the parts of a streamed reply, which this thread has sent; a read's
        one-frame reply is a list of buffers (:func:`P.reply_pieces`).  Runs on
        the executor thread pool.  A TRACE_FLAG'd frame carries a
        trace-context prefix: the op executes under a server span parented
        to the client's wire span, so the client can stitch the server-side
        time into ONE trace via the Op.TRACE round.  The span's ``queued_s``
        runs from the frame's body being read to this thread starting the
        op; ``read_s`` and ``read_calls`` are that read."""
        t_run = time.perf_counter()
        req_id, raw_op, cur = P.split_frame(frame.body)
        opcode, traced = P.mask_op(raw_op)
        ctx = None
        if traced:
            tid, sid = P.decode_trace_ctx(cur)
            self._ensure_tracer()
            ctx = SpanContext(tid, sid)
        if opcode == Op.TRACE:
            # the extended STATS round: hand the accumulated server spans
            # to the client (drained — each round returns fresh spans)
            spans = [s.to_dict() for s in self.tracer.drain()]
            return Op.OK, P.pack_str(json.dumps(spans))
        tr = self.tracer
        with tr.span(_SERVER_SPANS.get(opcode, "server.op"), remote_parent=ctx) as sp:
            if tr.enabled:
                sp.set("op", Op.NAMES.get(opcode, hex(opcode)))
                sp.set("queued_s", t_run - frame.t_read)
                sp.set("read_s", frame.read_s)
                sp.set("read_calls", frame.read_calls)
            if opcode == Op.RETRIEVE_BATCH:
                keys = P.decode_keys(cur)
                return self._reply_fields(
                    opcode, list(zip(keys, self.fdb.retrieve_batch(keys))), conn, req_id, sp
                )
            if opcode == Op.RETRIEVE_MANY:
                fs = self.fdb.retrieve_many(P.decode_request(cur))
                return self._reply_fields(
                    opcode, list(zip(fs.keys, fs.handles())), conn, req_id, sp
                )
            return Op.OK, self._dispatch_op(opcode, cur)

    def _reply_fields(self, opcode: int, handles: list, conn: _Connection, req_id: int,
                      sp) -> tuple[int, bytes | list]:
        """A read's reply to ``(key, handle)`` items: one frame, as the
        payload's pieces; or, past the part bound to a peer that streams,
        ``PART`` frames sent from this thread, each part read from the store
        just before it is written, and the next read only once the
        transport has drained (module docstring); then the ``END`` payload.
        The payloads go to the transport as they were read, never joined
        into a frame."""
        sizes = [(k, None if h is None else h.size) for k, h in handles]
        ranges = P.split_reply(opcode, sizes, P.part_bound(self._max_frame))
        if len(ranges) == 1 or conn.ext < P.STREAM_EXT_VERSION:
            return Op.OK, P.reply_pieces(opcode, _read_items(handles))
        tr, stats = self.tracer, self.wire_stats
        most = 0
        for r in ranges:
            t0 = time.perf_counter()
            with tr.span(_PART_SPANS[opcode]) as part:
                pieces = P.reply_pieces(opcode, _read_items(handles[r.start:r.stop]))
                frame = [P.frame_head(req_id, Op.PART, P.pieces_len(pieces)), *pieces]
                size = P.pieces_len(frame)
                most = max(most, size)
                if tr.enabled:
                    part.set("bytes", size - 4)
                    part.set("fields", len(r))
                asyncio.run_coroutine_threadsafe(conn.send_frame(frame), self._loop).result()
            stats.record("wire_part", seconds=time.perf_counter() - t0, nbytes_w=size,
                         shard=conn.name)
        with stats.lock:
            stats.counters["wire_part_buffered_max"] = max(
                stats.counters["wire_part_buffered_max"], most)
        if tr.enabled:
            sp.set("parts", len(ranges))
            sp.set("buffered_max", most)
        return Op.END, P.encode_end(len(ranges), len(handles))

    def _dispatch_op(self, opcode: int, cur: Cursor) -> bytes:
        if opcode == Op.LIST:
            return P.encode_listing(self.fdb.list(P.decode_request(cur)))
        if opcode == Op.WIPE:
            return P.encode_wipe_report(self.fdb.wipe(P.decode_request(cur)))
        if opcode == Op.FLUSH:
            self.fdb.flush()
            return b""
        if opcode == Op.STATS:
            snap = {
                "server": self.fdb.stats_snapshot(),
                "wire": self.wire_stats.snapshot(),
            }
            return P.pack_str(json.dumps(snap, sort_keys=True))
        if opcode == Op.HELLO:
            raise ProtocolError("duplicate handshake on an established connection")
        raise ProtocolError(f"unknown opcode {opcode:#x}")


def _read_items(handles) -> list[tuple]:
    """``(key, payload)`` of ``(key, handle)`` items, each handle read and
    closed (an absent field stays ``None``)."""
    items: list[tuple] = []
    for key, h in handles:
        if h is None:
            items.append((key, None))
        else:
            try:
                items.append((key, h.read()))
            finally:
                h.close()
    return items


def _base_op(frame: _Frame) -> int:
    """A frame's opcode without the TRACE_FLAG bit."""
    return P.mask_op(P.split_frame(frame.body)[1])[0]


def serve_fdb(fdb, *, host: str = "127.0.0.1", port: int = 0, **kw) -> FDBServer:
    """Start an :class:`FDBServer` over *fdb*; returns the RUNNING server
    (``server.addr`` is the bound address)."""
    server = FDBServer(fdb, host=host, port=port, **kw)
    server.start()
    return server


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="Serve an FDB composition tree over the wire protocol"
    )
    ap.add_argument("--config", required=True, metavar="JSON|PATH",
                    help="FDB config (repro.core.config grammar): inline JSON "
                         "or a path to a JSON file")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="TCP port (0 = ephemeral; the bound port is printed)")
    ap.add_argument("--max-inflight", type=int, default=32,
                    help="per-connection backpressure bound (pipelined frames)")
    args = ap.parse_args()

    if args.config.lstrip().startswith("{"):
        cfg = json.loads(args.config)
    else:
        with open(args.config) as f:
            cfg = json.load(f)

    server = FDBServer(cfg, host=args.host, port=args.port,
                       max_inflight=args.max_inflight)
    host, port = server.start()
    print(f"FDB server listening on {host}:{port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()


if __name__ == "__main__":
    main()
