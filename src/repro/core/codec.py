"""The GRIB codec on the wire path (paper §1.2, ROADMAP "Pallas GRIB codec").

Real FDB traffic is GRIB: every field is bit-packed (scale/offset + n-bit
codes) before it touches the object store, so the bandwidth that matters
operationally is the *effective* (pre-codec) field throughput, not the wire
byte rate — both DAOS-vs-Lustre studies (arXiv 2404.03107, 2211.09162)
report field throughput.  This module fuses the
:mod:`repro.kernels.grib_pack` Pallas kernels into the archive/retrieve hot
path:

- :func:`encode_fields` packs a WHOLE batch of ``(F, H, W)`` fields in one
  ``grib_pack`` kernel launch (one launch per distinct field shape when the
  batch is ragged) and frames each field as a self-describing wire payload;
- :func:`decode_payloads` batch-unpacks payloads the same way (one
  ``grib_unpack`` launch per shape group);
- :class:`DecodedFieldSet` is the lazy read-side view: a partial
  ``retrieve_many`` slice decodes chunk by chunk, each chunk in one kernel
  launch, as it is consumed; the whole set decodes part by part as the
  parts of a streamed reply land, into one ``(F, H, W)`` array;
- :class:`CodecFDB` is the declarative facade — ``{"type": "codec",
  "nbits": 16, "inner": {...}}`` in :func:`~repro.core.config.build_fdb` —
  that fixes the pack width per tier, so a hot DAOS tier can pack at 16
  bits while the cold POSIX archive keeps 24.

Wire payload layout (little-endian, 32-byte header + code stream)::

    offset  size  field
    0       4     magic  b"GRPK"
    4       1     version (=1)
    5       1     nbits   (code width; container dtype is derived from it)
    6       2     reserved (zero)
    8       4     height  (uint32)
    12      4     width   (uint32)
    16      8     ref     (float64 — per-field reference value, i.e. min)
    24      8     scale   (float64 — quantisation step)
    32      H*W*itemsize  codes (uint8/uint16/uint32 from ``payload_dtype``)

The header makes codec'd and raw datasets coexist in one catalogue:
:func:`is_codec_payload` distinguishes them, and the byte-level client
surface (``retrieve``/``read``/``list``/``wipe``) never looks inside.
Telemetry: every pack/unpack records wire bytes AND effective (pre-codec)
bytes into the owning client's codec :class:`~repro.metrics.IOStats` sink,
so ``stats_snapshot()`` reports the compression win.
"""

from __future__ import annotations

import queue
import struct
import threading
import time
from typing import Iterator, Mapping, Sequence

import numpy as np

from ..kernels.grib_pack import grib_pack, grib_unpack, payload_dtype
from ..obs.tracer import NULL_TRACER
from .client import FDBClient, WipeReport
from .datahandle import DataHandle
from .fieldset import FieldResolutionError, FieldSet
from .keys import Key
from .request import Request
from .schema import Schema

__all__ = [
    "CODEC_HEADER_SIZE",
    "CodecError",
    "CodecFDB",
    "CodecHeader",
    "DecodedFieldSet",
    "decode_payloads",
    "encode_fields",
    "is_codec_payload",
    "parse_header",
    "take_fields",
    "wire_size",
]


class CodecError(ValueError):
    """A payload that is not (or not consistently) a codec wire frame."""


_MAGIC = b"GRPK"
_VERSION = 1
_HEADER_FMT = "<4sBBHIIdd"  # magic, version, nbits, reserved, H, W, ref, scale
CODEC_HEADER_SIZE = struct.calcsize(_HEADER_FMT)  # 32 bytes

class CodecHeader:
    """Parsed wire header of one codec payload."""

    __slots__ = ("nbits", "height", "width", "ref", "scale")

    def __init__(self, nbits: int, height: int, width: int, ref: float, scale: float):
        self.nbits = nbits
        self.height = height
        self.width = width
        self.ref = ref
        self.scale = scale

    @property
    def dtype(self) -> np.dtype:
        return payload_dtype(self.nbits)

    @property
    def body_size(self) -> int:
        return self.height * self.width * self.dtype.itemsize

    def __repr__(self) -> str:
        return (
            f"CodecHeader(nbits={self.nbits}, shape=({self.height}, "
            f"{self.width}), ref={self.ref!r}, scale={self.scale!r})"
        )


def wire_size(shape: tuple[int, int], nbits: int) -> int:
    """Exact wire bytes of one encoded (H, W) field at ``nbits``."""
    h, w = shape
    return CODEC_HEADER_SIZE + h * w * payload_dtype(nbits).itemsize


def is_codec_payload(data: bytes) -> bool:
    """True when *data* starts with a codec wire header (raw payloads in the
    same catalogue return False — coexistence is a header check away)."""
    return len(data) >= CODEC_HEADER_SIZE and data[:4] == _MAGIC


def parse_header(payload: bytes, *, context: str = "") -> CodecHeader:
    """Parse and validate one payload's header; :class:`CodecError` names
    what is wrong (and for which field, when the caller supplies context)."""
    where = f" for {context}" if context else ""
    if len(payload) < CODEC_HEADER_SIZE:
        raise CodecError(
            f"payload{where} is {len(payload)} bytes — shorter than the "
            f"{CODEC_HEADER_SIZE}-byte codec header (raw, truncated, or not "
            "a codec payload)"
        )
    magic, version, nbits, _reserved, h, w, ref, scale = struct.unpack_from(
        _HEADER_FMT, payload
    )
    if magic != _MAGIC:
        raise CodecError(
            f"payload{where} does not carry the codec magic {_MAGIC!r} — "
            "this dataset was archived raw; retrieve it with the byte-level "
            "API (retrieve/read) instead of retrieve_fields"
        )
    if version != _VERSION:
        raise CodecError(f"unsupported codec payload version {version}{where}")
    hdr = CodecHeader(nbits, h, w, ref, scale)
    body = len(payload) - CODEC_HEADER_SIZE
    if body != hdr.body_size:
        raise CodecError(
            f"payload{where} declares a ({h}, {w}) field of {nbits}-bit codes "
            f"({hdr.body_size} bytes, {hdr.dtype.name} container) but carries "
            f"{body} bytes — corrupt or mis-framed"
        )
    return hdr


def take_fields(fields, idxs: Sequence[int]):
    """Index a field batch — an ``(F, H, W)`` array or a sequence of 2-D
    arrays — by positions (routing facades split batches per tier/lane).
    A run of consecutive positions of an array is a view of it, not a copy."""
    if isinstance(fields, np.ndarray):
        lo = idxs[0] if len(idxs) else 0
        if list(idxs) == list(range(lo, lo + len(idxs))):
            return fields[lo:lo + len(idxs)]
        return fields[np.asarray(idxs, dtype=np.intp)]
    return [fields[i] for i in idxs]


def _as_field_list(fields) -> list[np.ndarray]:
    """Normalise the accepted batch forms to a list of 2-D float32 fields."""
    if isinstance(fields, np.ndarray):
        if fields.ndim == 2:
            fields = fields[None]
        if fields.ndim != 3:
            raise CodecError(
                f"fields must be (F, H, W) or a sequence of (H, W) arrays, "
                f"got ndim={fields.ndim}"
            )
        arr = np.asarray(fields, dtype=np.float32)
        return [arr[i] for i in range(arr.shape[0])]
    out = []
    for i, f in enumerate(fields):
        f = np.asarray(f, dtype=np.float32)
        if f.ndim != 2:
            raise CodecError(f"field {i} must be 2-D (H, W), got shape {f.shape}")
        out.append(f)
    return out


def _stacked(fields, flist: list[np.ndarray], idxs: list[int]) -> np.ndarray:
    """The fields at ``idxs`` as one ``(f, H, W)`` float32 array: the
    caller's own array when it is that already, else a stacked copy."""
    if isinstance(fields, np.ndarray) and len(idxs) == len(flist):
        arr = fields[None] if fields.ndim == 2 else fields
        if arr.dtype == np.float32 and arr.flags.c_contiguous:
            return arr
    return np.stack([flist[i] for i in idxs])


def encode_fields(fields, *, nbits: int = 16, stats=None, tracer=None) -> list[bytes]:
    """Bit-pack a batch of fields into wire payloads.

    ``fields`` is an ``(F, H, W)`` array or a sequence of ``(H, W)`` arrays.
    The WHOLE batch goes through ONE ``grib_pack`` kernel launch (one per
    distinct shape when ragged) — the per-launch dispatch cost is amortised
    exactly like the backends amortise per-op I/O costs in
    ``archive_batch``.  Returns one payload per field, in input order.
    ``tracer`` records one ``codec.pack`` span per kernel launch with
    effective/wire bytes, and inside it ``codec.pack.stack`` (the batch
    stacked, unless it is one float32 array already), ``codec.pack.device``
    (the kernel call until codes, narrowed to their wire container on the
    device, ref and scale are host arrays) and ``codec.pack.frame`` (the
    framing).
    """
    payload_dtype(nbits)  # validates nbits before any device work
    tr = tracer if tracer is not None else NULL_TRACER
    flist = _as_field_list(fields)
    if not flist:
        return []
    t0 = time.perf_counter()
    payloads: list[bytes | None] = [None] * len(flist)
    groups: dict[tuple[int, int], list[int]] = {}
    for i, f in enumerate(flist):
        groups.setdefault(f.shape, []).append(i)
    for shape, idxs in groups.items():
        h, w = shape
        with tr.span("codec.pack") as sp:
            with tr.span("codec.pack.stack"):
                batch = _stacked(fields, flist, idxs)  # (f, H, W) float32
            with tr.span("codec.pack.device"):
                codes, ref, scale = grib_pack(batch, nbits=nbits)
                codes = np.asarray(codes)  # the wire's container, device to host
                ref = np.asarray(ref, dtype=np.float64)
                scale = np.asarray(scale, dtype=np.float64)
            with tr.span("codec.pack.frame"):
                for j, i in enumerate(idxs):
                    header = struct.pack(
                        _HEADER_FMT, _MAGIC, _VERSION, nbits, 0, h, w, ref[j], scale[j]
                    )
                    # one copy of the codes (a device-to-host array need not be
                    # C-contiguous field by field)
                    payloads[i] = b"".join((header, np.ascontiguousarray(codes[j])))
            if tr.enabled:
                sp.set("nbits", nbits)
                sp.set("fields", len(idxs))
                sp.set("shape", [h, w])
                sp.set("effective_bytes", len(idxs) * h * w * 4)
                sp.set("wire_bytes", len(idxs) * wire_size(shape, nbits))
    if stats is not None:
        # effective (pre-codec) bytes only — the WIRE bytes of these
        # payloads are counted by the backend sinks when they land, so the
        # merged snapshot's bytes_written stays the true wire total and
        # effective/wire is the compression win
        stats.record(
            "codec_pack",
            seconds=time.perf_counter() - t0,
            effective_w=sum(f.nbytes for f in flist),
            count=len(flist),
        )
    return payloads  # type: ignore[return-value]


def decode_payloads(
    payloads: Sequence[bytes | None],
    *,
    stats=None,
    labels: Sequence | None = None,
    tracer=None,
) -> list[np.ndarray | None]:
    """Unpack wire payloads back to float32 fields.

    ``None`` entries (absent fields) pass through.  All payloads decode in
    ONE ``grib_unpack`` kernel launch per distinct field shape.  ``labels``
    (e.g. the MARS keys) contextualise :class:`CodecError` messages.
    ``tracer`` records one ``codec.unpack`` span per kernel launch with
    effective/wire bytes, and inside it ``codec.unpack.stack`` (views of
    each payload's codes, uncopied, with ref and scale; the device stacks
    and widens them) and ``codec.unpack.device`` (the kernel call, copies
    in included, until the decoded host array).
    """
    tr = tracer if tracer is not None else NULL_TRACER
    t0 = time.perf_counter()
    out: list[np.ndarray | None] = [None] * len(payloads)
    headers: list[CodecHeader | None] = [None] * len(payloads)
    groups: dict[tuple[int, int, int], list[int]] = {}
    for i, p in enumerate(payloads):
        if p is None:
            continue
        ctx = str(labels[i]) if labels is not None else ""
        hdr = parse_header(p, context=ctx)
        headers[i] = hdr
        groups.setdefault((hdr.height, hdr.width, hdr.nbits), []).append(i)
    for (h, w, nbits), idxs in groups.items():
        with tr.span("codec.unpack") as sp:
            with tr.span("codec.unpack.stack"):
                dtype = payload_dtype(nbits)
                # each field's codes where they lie in its payload: the
                # device stacks and widens them
                codes = tuple(
                    np.frombuffer(payloads[i], dtype=dtype, count=h * w, offset=CODEC_HEADER_SIZE)
                    .reshape(h, w)
                    for i in idxs
                )
                ref = np.asarray([headers[i].ref for i in idxs], dtype=np.float32)
                scale = np.asarray([headers[i].scale for i in idxs], dtype=np.float32)
            with tr.span("codec.unpack.device"):
                decoded = np.asarray(grib_unpack(codes, ref, scale))
            for j, i in enumerate(idxs):
                out[i] = decoded[j]
            if tr.enabled:
                sp.set("nbits", nbits)
                sp.set("fields", len(idxs))
                sp.set("shape", [h, w])
                sp.set("effective_bytes", len(idxs) * h * w * 4)
                sp.set("wire_bytes", sum(len(payloads[i]) for i in idxs))
    if stats is not None:
        # effective bytes only; the wire reads were counted by the backend
        stats.record(
            "codec_unpack",
            seconds=time.perf_counter() - t0,
            effective_r=sum(a.nbytes for a in out if a is not None),
            count=sum(1 for p in payloads if p is not None),
        )
    return out


def _payload_bytes(handles: list) -> int:
    return sum(h.size for h in handles if h is not None)


class _PartFeed:
    """Takes the rest of a FieldSet's parts on a thread of its own, once the
    consumer holds the first, holding at most two parts (the one the
    consumer decodes and the next), so that the next part lands while the
    current one decodes.  ``buffered_max`` is the most payload bytes held in
    parts at once: received, and not yet decoded, ``held`` (the first
    part's) included."""

    def __init__(self, parts: Iterator, held: int):
        self._parts = parts
        self._slots = threading.Semaphore(1)  # the first part holds the other
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._stop = False
        self._mu = threading.Lock()
        self._held = held
        self.buffered_max = held
        self._thread = threading.Thread(target=self._run, daemon=True, name="codec-part-feed")
        self._thread.start()

    def _run(self) -> None:
        try:
            while True:
                self._slots.acquire()
                part = None if self._stop else next(self._parts, None)
                if part is None:
                    break
                nbytes = _payload_bytes(part[1])
                with self._mu:
                    self._held += nbytes
                    self.buffered_max = max(self.buffered_max, self._held)
                self._q.put((part, nbytes))
            self._q.put(None)
        except BaseException as e:  # raised again in the consumer, which would wait otherwise
            self._q.put(e)
            if not isinstance(e, Exception):
                raise
        finally:
            close = getattr(self._parts, "close", None)
            if close is not None:
                close()

    def get(self):
        """The next ``(part, nbytes)``, or ``None`` after the last part."""
        item = self._q.get()
        if isinstance(item, BaseException):
            raise item
        return item

    def done(self, nbytes: int) -> None:
        """The consumer has decoded a part of ``nbytes``."""
        with self._mu:
            self._held -= nbytes
        self._slots.release()

    def close(self) -> None:
        """Stop taking parts (the one in flight is still received)."""
        self._stop = True
        self._slots.release()


class DecodedFieldSet:
    """The lazy result of :meth:`FDBClient.retrieve_fields`.

    Wraps a :class:`~repro.core.fieldset.FieldSet` and decodes on first
    touch, chunk by chunk — iterating a partial ``retrieve_many`` slice
    pays one backend fetch AND one ``grib_unpack`` launch per chunk, never
    per field.  :meth:`read_all` and :meth:`arrays` take what is left of
    the set part by part instead, as the parts of a streamed reply land
    (the FieldSet's part source): one launch per part, written into one
    preallocated ``(F, H, W)`` array.  The first part is taken on the
    caller's thread; only when the reply goes on past it is the rest
    received on a thread of the set's own, the next part landing while the
    current one decodes.  Decoded arrays are memoised; the underlying byte
    handles are read and closed as each chunk or part resolves.
    ``buffered_max`` is the most payload bytes held in received parts at
    once, where the parts went through that thread.
    """

    def __init__(
        self, fieldset: FieldSet, *, chunk: int | None = 64, stats=None, tracer=None
    ):
        self._fs = fieldset
        self._chunk = max(1, len(fieldset) if chunk is None else chunk)
        self._stats = stats
        self._tracer = tracer
        self._arrays: list[np.ndarray | None | type(...)] = [...] * len(fieldset)
        self._out: np.ndarray | None = None  # the (F, H, W) array parts decode into
        self.buffered_max = 0
        self._mu = threading.Lock()

    # ------------------------------------------------------------- resolution
    def _decode_range(self, lo: int, hi: int) -> None:
        with self._mu:
            idxs = [j for j in range(lo, hi) if self._arrays[j] is ...]
            if not idxs:
                return
            payloads: list[bytes | None] = []
            for j in idxs:
                h = self._fs.handle_at(j)
                if h is None:
                    payloads.append(None)
                else:
                    try:
                        payloads.append(h.read())
                    finally:
                        h.close()
            decoded = decode_payloads(
                payloads,
                stats=self._stats,
                labels=[self._fs.keys[j] for j in idxs],
                tracer=self._tracer,
            )
            for j, a in zip(idxs, decoded):
                self._arrays[j] = a

    def _decode_parts(self) -> None:
        """Decode every field not decoded yet, part by part as the parts land
        (see class docstring).  ``codec.unpack.wait`` spans time each wait
        for a part that has not landed yet."""
        tr = self._tracer if self._tracer is not None else NULL_TRACER
        with self._mu:
            if all(a is not ... for a in self._arrays):
                return
            parts = self._fs.parts()
            feed: _PartFeed | None = None
            try:
                while True:
                    with tr.span("codec.unpack.wait"):
                        part = next(parts, None) if feed is None else feed.get()
                    if part is None:
                        break
                    if feed is None:
                        nbytes = _payload_bytes(part[1])
                        if part[2]:  # the reply goes on: take the rest meanwhile
                            feed = _PartFeed(parts, nbytes)
                    else:
                        part, nbytes = part
                    self._decode_part(*part[:2])
                    if feed is not None:
                        feed.done(nbytes)
            finally:
                if feed is None:
                    close = getattr(parts, "close", None)
                    if close is not None:
                        close()
                else:
                    feed.close()
                    self.buffered_max = feed.buffered_max
                    if self._stats is not None:
                        with self._stats.lock:
                            c = self._stats.counters
                            c["unpack_part_buffered_max"] = max(
                                c["unpack_part_buffered_max"], feed.buffered_max)
            left = sum(a is ... for a in self._arrays)
            if left:
                keys = [k for k, a in zip(self._fs.keys, self._arrays) if a is ...]
                raise FieldResolutionError(len(self._arrays), len(self._arrays) - left, keys)

    def _decode_part(self, pos: list[int], handles: list) -> None:
        """Decode the positions of ``pos`` not decoded yet (a chunk decoded
        before has read and closed its handles already)."""
        todo = [(j, h) for j, h in zip(pos, handles) if self._arrays[j] is ...]
        pos = [j for j, _ in todo]
        payloads: list[bytes | None] = []
        for _, h in todo:
            if h is None:
                payloads.append(None)
            else:
                try:
                    payloads.append(h.read())
                finally:
                    h.close()
        decoded = decode_payloads(
            payloads,
            stats=self._stats,
            labels=[self._fs.keys[j] for j in pos],
            tracer=self._tracer,
        )
        for j, a in zip(pos, decoded):
            if a is not None and self._out is None:
                self._out = np.empty((len(self._arrays), *a.shape), np.float32)
            if a is not None and a.shape == self._out.shape[1:]:
                self._out[j] = a
                a = self._out[j]
            self._arrays[j] = a

    # -------------------------------------------------------------- container
    @property
    def keys(self) -> tuple[Key, ...]:
        return self._fs.keys

    def __len__(self) -> int:
        return len(self._fs)

    def __iter__(self) -> Iterator[tuple[Key, np.ndarray | None]]:
        n = len(self._fs)
        for lo in range(0, n, self._chunk):
            hi = min(lo + self._chunk, n)
            self._decode_range(lo, hi)
            for j in range(lo, hi):
                yield self._fs.keys[j], self._arrays[j]

    def items(self) -> Iterator[tuple[Key, np.ndarray | None]]:
        return iter(self)

    def __getitem__(self, key: Key | Mapping[str, str]) -> np.ndarray | None:
        key = key if isinstance(key, Key) else Key(key)
        try:
            i = self._fs.keys.index(key)
        except ValueError:
            raise KeyError(key) from None
        lo = (i // self._chunk) * self._chunk
        self._decode_range(lo, min(lo + self._chunk, len(self._fs)))
        return self._arrays[i]

    def __repr__(self) -> str:
        resolved = sum(1 for a in self._arrays if a is not ...)
        return f"DecodedFieldSet({len(self._arrays)} fields, {resolved} decoded)"

    # ------------------------------------------------------------ convenience
    def read_all(self) -> dict[Key, np.ndarray | None]:
        """Decode everything not decoded yet: part by part as the parts land
        when nothing was, else the rest in ONE whole-batch backend fetch and
        one unpack launch per field shape.  Once all is decoded, nothing is
        fetched again."""
        self._decode_parts()
        return dict(zip(self._fs.keys, self._arrays))

    def missing(self) -> list[Key]:
        """Keys whose field is absent from the FDB."""
        self.read_all()
        return [k for k, a in zip(self._fs.keys, self._arrays) if a is None]

    def arrays(self) -> np.ndarray:
        """The whole set as one ``(F, H, W)`` array — the array the parts
        were decoded into, not a copy, when :meth:`read_all` took them —
        raises :class:`CodecError` when fields are absent or shapes are
        ragged."""
        all_ = self.read_all()
        absent = [k for k, a in all_.items() if a is None]
        if absent:
            raise CodecError(f"cannot stack: {len(absent)} absent fields {absent[:3]}")
        mats = [self._arrays[j] for j in range(len(self._fs))]
        shapes = {a.shape for a in mats}
        if len(shapes) > 1:
            raise CodecError(f"cannot stack ragged field shapes {sorted(shapes)}")
        if self._out is not None and all(a.base is self._out for a in mats):
            return self._out
        return np.stack(mats)


class CodecFDB(FDBClient):
    """A codec tier: any inner :class:`FDBClient` with the pack width fixed
    declaratively (``{"type": "codec", "nbits": N, "inner": ...}``).

    Byte-level operations pass straight through — raw and codec'd datasets
    coexist in the inner catalogue — while :meth:`archive_fields` packs at
    this tier's ``nbits`` (the whole batch in one kernel launch) and
    :meth:`retrieve_fields` decodes lazily per chunk.  The codec telemetry
    sink rides in :meth:`io_stats`, so effective-vs-wire bytes surface in
    every ``stats_snapshot()`` up the composition tree.
    """

    def __init__(self, inner: FDBClient, *, nbits: int = 16, owns_inner: bool = True):
        payload_dtype(nbits)  # validate the width before accepting the tier
        self.inner = inner
        self.schema: Schema = inner.schema
        self._codec_nbits = nbits
        self._owns_inner = owns_inner
        self._fieldset_batch = inner._fieldset_batch

    @property
    def nbits(self) -> int:
        return self._codec_nbits

    # ------------------------------------------------------------ pass-through
    def archive(self, key: Key | Mapping[str, str], data: bytes) -> None:
        self.inner.archive(key, data)

    def archive_batch(self, items) -> None:
        self.inner.archive_batch(items)

    def retrieve(self, key: Key | Mapping[str, str]) -> DataHandle | None:
        return self.inner.retrieve(key)

    def retrieve_batch(self, keys) -> list[DataHandle | None]:
        return self.inner.retrieve_batch(keys)

    def retrieve_batch_parts(self, keys):
        return self.inner.retrieve_batch_parts(keys)

    def retrieve_many(self, request) -> FieldSet:
        # the inner facade's fan-out/amortisation (AsyncFDB reader pool,
        # router scatter) must drive the fetch, not this wrapper's default
        return self.inner.retrieve_many(request)

    def flush(self) -> None:
        self.inner.flush()

    def drain(self) -> None:
        self.inner.drain()

    def _list(self, request: Request):
        return getattr(self.inner, "_list", self.inner.list)(request)

    def _wipe_dataset(self, dataset_key: Key, entries=None) -> WipeReport:
        return self.inner._wipe_dataset(dataset_key, entries)

    # ------------------------------------------------------------- telemetry
    def io_stats(self) -> list:
        return list(self.inner.io_stats()) + self._codec_sinks()

    # ------------------------------------------------------------- lifecycle
    def close(self) -> None:
        if self._owns_inner:
            self.inner.close()
        else:
            self.inner.flush()

    def __repr__(self) -> str:
        return f"CodecFDB(nbits={self._codec_nbits}, inner={self.inner!r})"
