"""The GRIB codec on the wire path: payload format, batch-fused kernels,
client surface (archive_fields/retrieve_fields), per-tier config widths,
effective-vs-wire telemetry, and the hammer's codec cells."""

import json
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from proptest import Rand, forall
from repro.core import (
    CODEC_HEADER_SIZE,
    AsyncFDB,
    CodecError,
    CodecFDB,
    FDBConfig,
    Key,
    NWP_SCHEMA_DAOS,
    NWP_SCHEMA_POSIX,
    SelectFDB,
    build_fdb,
    decode_payloads,
    encode_fields,
    is_codec_payload,
    make_fdb,
    wire_size,
)
from repro.core.codec import (
    DecodedFieldSet,
    parse_header,
    take_fields,
)
from repro.core.config import ConfigError
from repro.core.daos import DaosEngine
from repro.kernels.grib_pack import (
    grib_pack,
    grib_unpack,
    pack_to_bytes,
    payload_dtype,
    unpack_from_bytes,
)
from repro.kernels.grib_pack.ref import field_stats, pack_ref, unpack_ref
from repro.metrics.iostats import IOStats
from repro.obs import Tracer, install_tracer

NBITS_SWEEP = (8, 16, 24)


def temperature_fields(rng, f, h, w):
    return (rng.standard_normal((f, h, w)) * 40 + 250).astype(np.float32)


def launches(tracer: Tracer) -> dict:
    """Kernel launches a tracer saw: one ``codec.pack``/``codec.unpack``
    span per launch."""
    names = [s.name for s in tracer.spans()]
    return {"pack": names.count("codec.pack"), "unpack": names.count("codec.unpack")}


def example_key(**over) -> Key:
    base = dict(
        **{"class": "od"}, stream="oper", expver="0001", date="20231201",
        time="1200", type="ef", levtype="sfc", number="1", levelist="1",
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


# ---------------------------------------------------------------------------
# satellite 1+2: pack_to_bytes/unpack_from_bytes honour nbits and the meta
# ---------------------------------------------------------------------------

class TestPackToBytes:
    @pytest.mark.parametrize("nbits", NBITS_SWEEP)
    def test_payload_width_follows_nbits(self, nbits):
        x = temperature_fields(np.random.default_rng(0), 1, 16, 128)[0]
        payload, meta = pack_to_bytes(x, nbits=nbits)
        dtype = payload_dtype(nbits)
        assert meta["nbits"] == nbits
        assert meta["dtype"] == dtype.name
        assert len(payload) == x.size * dtype.itemsize

    def test_distinct_nbits_distinct_sizes(self):
        # the seed bug: nbits was accepted and ignored (always uint16)
        x = temperature_fields(np.random.default_rng(1), 1, 8, 128)[0]
        sizes = {n: len(pack_to_bytes(x, nbits=n)[0]) for n in NBITS_SWEEP}
        assert sizes[8] < sizes[16] < sizes[24]

    @pytest.mark.parametrize("nbits", NBITS_SWEEP)
    def test_roundtrip_within_quantum(self, nbits):
        x = temperature_fields(np.random.default_rng(2), 1, 32, 128)[0]
        payload, meta = pack_to_bytes(x, nbits=nbits)
        y = unpack_from_bytes(payload, meta)
        quantum = (x.max() - x.min()) / ((1 << nbits) - 1)
        assert np.max(np.abs(np.asarray(y) - x)) <= quantum * 1.01

    def test_unpack_rejects_mismatched_payload(self):
        x = temperature_fields(np.random.default_rng(3), 1, 8, 128)[0]
        payload, meta = pack_to_bytes(x, nbits=16)
        with pytest.raises(ValueError, match="do not belong together"):
            unpack_from_bytes(payload[:-2], meta)
        wrong = dict(meta, shape=(4, 128))
        with pytest.raises(ValueError, match="do not belong together"):
            unpack_from_bytes(payload, wrong)

    def test_unpack_legacy_meta_without_dtype(self):
        # meta written before the dtype field existed: fall back to nbits
        x = temperature_fields(np.random.default_rng(4), 1, 8, 128)[0]
        payload, meta = pack_to_bytes(x, nbits=8)
        del meta["dtype"]
        y = unpack_from_bytes(payload, meta)
        assert np.asarray(y).shape == x.shape

    def test_payload_dtype_containers(self):
        assert payload_dtype(8) == np.uint8
        assert payload_dtype(16) == np.uint16
        assert payload_dtype(24) == np.uint32
        with pytest.raises(ValueError):
            payload_dtype(0)
        with pytest.raises(ValueError):
            payload_dtype(33)


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------

class TestWireFormat:
    def test_header_roundtrip(self):
        fields = temperature_fields(np.random.default_rng(5), 3, 16, 128)
        for nbits in NBITS_SWEEP:
            payloads = encode_fields(fields, nbits=nbits)
            for p in payloads:
                assert is_codec_payload(p)
                hdr = parse_header(p)
                assert (hdr.nbits, hdr.height, hdr.width) == (nbits, 16, 128)
                assert len(p) == wire_size((16, 128), nbits) == CODEC_HEADER_SIZE + hdr.body_size

    def test_raw_payload_is_not_codec(self):
        assert not is_codec_payload(b"plain GRIB-less bytes, long enough to check")
        with pytest.raises(CodecError, match="archived raw"):
            parse_header(b"x" * 100)

    def test_truncated_and_misframed_payloads(self):
        with pytest.raises(CodecError, match="shorter than"):
            parse_header(b"GRPK")
        p = encode_fields(temperature_fields(np.random.default_rng(6), 1, 8, 128))[0]
        with pytest.raises(CodecError, match="carries"):
            parse_header(p[:-4])
        with pytest.raises(CodecError, match="version"):
            parse_header(p[:4] + b"\x09" + p[5:])

    def test_error_names_the_field(self):
        with pytest.raises(CodecError, match="step=42"):
            parse_header(b"y" * 100, context="step=42")


# ---------------------------------------------------------------------------
# batch encode/decode: one kernel launch per batch, bit-stable decode
# ---------------------------------------------------------------------------

class TestEncodeDecode:
    @pytest.mark.parametrize("nbits", NBITS_SWEEP)
    @pytest.mark.parametrize("given", ["array", "list", "float64"])
    def test_payloads_hold_the_kernels_codes_at_container_width(self, nbits, given):
        fields = temperature_fields(np.random.default_rng(11), 3, 16, 128)
        arg = {"array": fields, "list": list(fields), "float64": fields.astype(np.float64)}[given]
        payloads = encode_fields(arg, nbits=nbits)
        codes, ref, scale = grib_pack(fields, nbits=nbits)
        dtype = payload_dtype(nbits)
        for j, p in enumerate(payloads):
            assert type(p) is bytes and len(p) == wire_size((16, 128), nbits)
            hdr = parse_header(p)
            assert (hdr.ref, hdr.scale) == (float(ref[j]), float(scale[j]))
            assert p[CODEC_HEADER_SIZE:] == np.asarray(codes[j]).astype(dtype).tobytes()

    def test_payloads_from_codes_laid_out_field_minor(self, monkeypatch):
        """A device-to-host array may come back in another layout than C
        order (the chip returns 16-bit batches so): the payloads hold the
        same bytes."""
        from repro.core import codec

        fields = temperature_fields(np.random.default_rng(13), 4, 16, 128)
        want = encode_fields(fields, nbits=16)
        real = codec.grib_pack

        def field_minor(x, **kw):
            codes, ref, scale = real(x, **kw)
            c = np.asarray(codes)
            return np.moveaxis(np.ascontiguousarray(np.moveaxis(c, 0, -1)), -1, 0), ref, scale

        monkeypatch.setattr(codec, "grib_pack", field_minor)
        assert not field_minor(fields, nbits=16)[0][1].flags.c_contiguous
        assert encode_fields(fields, nbits=16) == want

    def test_take_fields_views_a_run_and_copies_the_rest(self):
        fields = temperature_fields(np.random.default_rng(12), 6, 8, 128)
        run, scattered = take_fields(fields, [2, 3, 4]), take_fields(fields, [0, 2, 5])
        assert np.shares_memory(run, fields) and np.array_equal(run, fields[2:5])
        assert not np.shares_memory(scattered, fields)
        assert np.array_equal(scattered, fields[[0, 2, 5]])

    def test_one_pack_launch_per_uniform_batch(self):
        fields = temperature_fields(np.random.default_rng(7), 9, 16, 128)
        tr = Tracer()
        encode_fields(fields, nbits=16, tracer=tr)
        assert launches(tr) == {"pack": 1, "unpack": 0}

    def test_one_launch_per_shape_group_when_ragged(self):
        rng = np.random.default_rng(8)
        ragged = [temperature_fields(rng, 1, 8, 128)[0] for _ in range(3)]
        ragged += [temperature_fields(rng, 1, 16, 128)[0] for _ in range(2)]
        tr = Tracer()
        payloads = encode_fields(ragged, tracer=tr)
        assert launches(tr)["pack"] == 2
        tr = Tracer()
        decode_payloads(payloads, tracer=tr)
        assert launches(tr)["unpack"] == 2

    @pytest.mark.parametrize("kind, steps", [
        ("pack", ["stack", "device", "frame"]),
        ("unpack", ["stack", "device"]),
    ])
    def test_child_spans_nest_and_cover_the_launch(self, kind, steps):
        fields = temperature_fields(np.random.default_rng(10), 8, 256, 512)
        payloads = encode_fields(fields, nbits=16)
        decode_payloads(payloads)  # both programs compiled before timing
        tr = Tracer()
        if kind == "pack":
            encode_fields(fields, nbits=16, tracer=tr)
        else:
            decode_payloads(payloads, tracer=tr)
        spans = tr.spans()
        (parent,) = [s for s in spans if s.name == f"codec.{kind}"]
        children = sorted((s for s in spans if s.parent_id == parent.span_id),
                          key=lambda s: s.t0)
        assert [s.name for s in children] == [f"codec.{kind}.{st}" for st in steps]
        assert len(spans) == 1 + len(steps)
        for s in children:
            assert parent.t0 <= s.t0 <= s.t1 <= parent.t1
        for a, b in zip(children, children[1:]):
            assert a.t1 <= b.t0
        covered = sum(s.duration_s for s in children)
        assert covered >= 0.95 * parent.duration_s

    def test_decode_is_batchsplit_independent(self):
        # the lazy chunked read path must yield bit-identical floats no
        # matter how the payload list is split across unpack launches
        fields = temperature_fields(np.random.default_rng(9), 6, 16, 128)
        payloads = encode_fields(fields, nbits=16)
        whole = decode_payloads(payloads)
        split = [decode_payloads([p])[0] for p in payloads]
        for a, b in zip(whole, split):
            assert np.array_equal(a, b)

    def test_decode_matches_kernel_of_stored_codes_exactly(self):
        fields = temperature_fields(np.random.default_rng(10), 4, 16, 128)
        payloads = encode_fields(fields, nbits=16)
        decoded = decode_payloads(payloads)
        for p, d in zip(payloads, decoded):
            hdr = parse_header(p)
            codes = np.frombuffer(p, dtype=hdr.dtype, offset=CODEC_HEADER_SIZE)
            codes = codes.reshape(1, hdr.height, hdr.width).astype(np.int32)
            oracle = np.asarray(grib_unpack(
                jnp.asarray(codes),
                jnp.asarray([hdr.ref], dtype=jnp.float32),
                jnp.asarray([hdr.scale], dtype=jnp.float32),
            ))[0]
            assert np.array_equal(d, oracle)

    def test_codes_match_reference_packing(self):
        fields = temperature_fields(np.random.default_rng(11), 2, 16, 128)
        payloads = encode_fields(fields, nbits=16)
        ref, scale, inv_scale = field_stats(jnp.asarray(fields), nbits=16)
        expected = np.asarray(pack_ref(jnp.asarray(fields), ref, inv_scale, nbits=16))
        for i, p in enumerate(payloads):
            hdr = parse_header(p)
            codes = np.frombuffer(p, dtype=hdr.dtype, offset=CODEC_HEADER_SIZE)
            codes = codes.reshape(hdr.height, hdr.width).astype(np.int64)
            # rounding boundaries can flip ±1 code (test_kernels precedent)
            assert np.abs(codes - expected[i]).max() <= 1

    def test_none_passthrough_and_empty(self):
        assert encode_fields([]) == []
        assert decode_payloads([]) == []
        p = encode_fields(temperature_fields(np.random.default_rng(12), 1, 8, 128))[0]
        out = decode_payloads([None, p, None])
        assert out[0] is None and out[2] is None and out[1] is not None

    @forall()
    def test_roundtrip_error_within_quantum(self, r: Rand):
        nbits = r.choice(NBITS_SWEEP)
        f = r.int(1, 4)
        h = r.int(1, 24)
        x = (r.floats((f, h, 128), scale=40.0) + 250.0).astype(np.float32)
        decoded = decode_payloads(encode_fields(x, nbits=nbits))
        quantum = np.maximum(
            x.max(axis=(1, 2)) - x.min(axis=(1, 2)), 1e-30
        ) / ((1 << nbits) - 1)
        for i in range(f):
            err = np.max(np.abs(decoded[i] - x[i]))
            # at 24 bits the quantum drops below the float32 ulp of the
            # values themselves — representation precision is the floor
            ulp = np.spacing(np.float32(np.max(np.abs(x[i]))))
            assert err <= quantum[i] * 1.01 + 2 * ulp, f"nbits={nbits} err={err}"

    def test_take_fields_both_forms(self):
        arr = temperature_fields(np.random.default_rng(13), 4, 8, 128)
        assert np.array_equal(take_fields(arr, [2, 0])[0], arr[2])
        as_list = [arr[i] for i in range(4)]
        assert np.array_equal(take_fields(as_list, [3])[0], arr[3])


# ---------------------------------------------------------------------------
# satellite 3: end-to-end round trips through both backends
# ---------------------------------------------------------------------------

class TestClientRoundTrip:
    def _archive(self, fdb, nbits=None, steps=3, params=2):
        keys = [
            example_key(step=str(s), param=p)
            for s in range(steps) for p in ("u", "v", "t")[:params]
        ]
        rng = np.random.default_rng(42)
        fields = temperature_fields(rng, len(keys), 16, 128)
        fdb.archive_fields(keys, fields, nbits=nbits)
        fdb.flush()
        return keys, fields

    @pytest.mark.parametrize("nbits", NBITS_SWEEP)
    def test_archive_retrieve_fields(self, fdb, nbits):
        keys, fields = self._archive(fdb, nbits=nbits)
        req = {**dict(example_key()), "step": [str(s) for s in range(3)], "param": ["u", "v"]}
        got = fdb.retrieve_fields(req)
        assert len(got) == len(keys)
        arrs = got.arrays()
        assert arrs.shape == fields.shape
        quantum = np.maximum(
            fields.max(axis=(1, 2)) - fields.min(axis=(1, 2)), 1e-30
        ) / ((1 << nbits) - 1)
        # retrieve_many expands step-major, the archive was step-major too
        for k, a in got.items():
            i = keys.index(k)
            ulp = np.spacing(np.float32(np.max(np.abs(fields[i]))))  # 24-bit floor
            assert np.max(np.abs(a - fields[i])) <= quantum[i] * 1.01 + 2 * ulp

    def test_partial_retrieve_decodes_lazily_per_chunk(self, fdb):
        keys, fields = self._archive(fdb, steps=4, params=2)
        req = {**dict(example_key()), "step": ["0", "1", "2", "3"], "param": ["u", "v"]}
        fs = fdb.retrieve_many(req)
        tr = Tracer()
        decoded = DecodedFieldSet(fs, chunk=2, tracer=tr)
        first = decoded[keys[0]]
        assert first is not None
        # touching one key decodes ONE chunk in ONE launch, not the set
        assert launches(tr)["unpack"] == 1
        whole = fdb.retrieve_fields(req).read_all()
        for k, a in whole.items():
            assert np.array_equal(a, decoded[k])  # chunking never changes bits

    def test_missing_fields_pass_through_as_none(self, fdb):
        keys, _ = self._archive(fdb)
        req = {**dict(example_key()), "step": ["0", "99"], "param": "u"}
        got = fdb.retrieve_fields(req)
        assert got.missing() == [example_key(step="99", param="u")]
        with pytest.raises(CodecError, match="absent"):
            got.arrays()

    def test_raw_and_codec_coexist(self, fdb):
        raw_key = example_key(param="q")
        raw_payload = b"raw-grib-payload" * 4  # longer than the codec header
        fdb.archive(raw_key, raw_payload)
        keys, fields = self._archive(fdb, steps=1, params=1)
        # byte-level surface never looks inside either
        assert fdb.read(raw_key) == raw_payload
        assert is_codec_payload(fdb.read(keys[0]))
        # decoding the raw dataset names the problem
        got = fdb.retrieve_fields({**dict(raw_key)})
        with pytest.raises(CodecError, match="archived raw"):
            got.read_all()

    def test_effective_vs_wire_telemetry(self, fdb):
        keys, fields = self._archive(fdb, nbits=16)
        req = {**dict(example_key()), "step": [str(s) for s in range(3)], "param": ["u", "v"]}
        fdb.retrieve_fields(req).read_all()
        snap = fdb.stats_snapshot()
        raw = fields.nbytes
        assert snap["effective_bytes_written"] == raw
        assert snap["effective_bytes_read"] == raw
        # acceptance: 16-bit packing of float32 moves >=1.5x the wire bytes
        wire = len(keys) * wire_size((16, 128), 16)
        assert raw / wire >= 1.5
        assert snap["ops"]["codec_pack"] == len(keys)
        assert snap["ops"]["codec_unpack"] == len(keys)

    def test_archive_fields_key_count_mismatch(self, fdb):
        fields = temperature_fields(np.random.default_rng(0), 2, 8, 128)
        with pytest.raises(ValueError, match="2 keys for 3 fields|3 keys for 2"):
            fdb.archive_fields([example_key(), example_key(param="u"), example_key(param="t")],
                               fields)


# ---------------------------------------------------------------------------
# config node, per-tier widths, facade pass-through
# ---------------------------------------------------------------------------

class TestCodecConfig:
    def test_build_codec_node(self, tmp_path):
        cfg = {
            "type": "codec", "nbits": 8,
            "inner": {"backend": "posix", "schema": "nwp-posix",
                      "root": str(tmp_path / "f")},
        }
        with build_fdb(cfg) as fdb:
            assert isinstance(fdb, CodecFDB)
            assert fdb.nbits == 8
            keys = [example_key(param=p) for p in ("u", "v")]
            fdb.archive_fields(keys, temperature_fields(np.random.default_rng(0), 2, 8, 128))
            fdb.flush()
            assert parse_header(fdb.read(keys[0])).nbits == 8

    def test_config_json_roundtrip(self, tmp_path):
        cfg = FDBConfig({
            "type": "codec", "nbits": 24,
            "inner": {"backend": "posix", "schema": "nwp-posix",
                      "root": str(tmp_path / "f")},
        })
        again = FDBConfig.from_json(cfg.to_json())
        assert again == cfg
        with again.build() as fdb:
            assert fdb.nbits == 24

    def test_validation_errors(self, tmp_path):
        with pytest.raises(ConfigError, match="requires 'inner'"):
            build_fdb({"type": "codec"})
        with pytest.raises(ConfigError, match="nbits"):
            build_fdb({"type": "codec", "nbits": 0,
                       "inner": {"backend": "posix", "schema": "nwp-posix", "root": "/x"}})
        with make_fdb("posix", schema=NWP_SCHEMA_POSIX, root=str(tmp_path / "f")) as inner:
            with pytest.raises(ValueError, match="nbits"):
                CodecFDB(inner, nbits=40)

    def test_per_tier_widths_through_select(self, tmp_path):
        eng = DaosEngine()
        cfg = {
            "type": "select",
            "rules": [{
                "match": "number=0",
                "fdb": {"type": "codec", "nbits": 16,
                        "inner": {"backend": "daos", "schema": "nwp-daos", "engine": eng}},
            }],
            "default": {"type": "codec", "nbits": 24,
                        "inner": {"backend": "posix", "schema": "nwp-posix",
                                  "root": str(tmp_path / "cold")}},
        }
        with build_fdb(cfg) as fdb:
            assert isinstance(fdb, SelectFDB)
            hot = example_key(number="0")
            cold = example_key(number="5")
            fields = temperature_fields(np.random.default_rng(1), 2, 8, 128)
            tr = Tracer()
            install_tracer(fdb, tr)
            fdb.archive_fields([hot, cold], fields)  # ONE call, two widths
            assert launches(tr)["pack"] == 2  # one launch per tier
            fdb.flush()
            assert parse_header(fdb.read(hot)).nbits == 16
            assert parse_header(fdb.read(cold)).nbits == 24
            got = fdb.retrieve_fields({**dict(hot), "number": ["0", "5"]})
            arrs = got.arrays()
            assert arrs.shape == fields.shape
            snap = fdb.stats_snapshot()
            assert snap["effective_bytes_written"] == fields.nbytes

    def test_async_facade_inherits_codec_width(self, tmp_path):
        inner = CodecFDB(
            make_fdb("posix", schema=NWP_SCHEMA_POSIX, root=str(tmp_path / "f")),
            nbits=8,
        )
        with AsyncFDB(inner, writers=1, owns_fdb=True) as afdb:
            assert afdb._codec_nbits == 8
            k = example_key()
            afdb.archive_fields([k], temperature_fields(np.random.default_rng(2), 1, 8, 128))
            afdb.flush()
            assert parse_header(afdb.read(k)).nbits == 8

    def test_codec_over_prebuilt_inner_stays_caller_owned(self, tmp_path):
        inner = make_fdb("posix", schema=NWP_SCHEMA_POSIX, root=str(tmp_path / "f"))
        with build_fdb({"type": "codec", "inner": inner}) as fdb:
            assert fdb.inner is inner
        # the pass-through inner survives the wrapper's close
        inner.archive(example_key(), b"still-open")
        inner.close()


# ---------------------------------------------------------------------------
# roofline probes: the codec is memory-bound by a wide margin
# ---------------------------------------------------------------------------

class TestCodecRoofline:
    def test_pack_and_unpack_are_memory_bound(self):
        from repro.roofline import codec_roofline, ridge_intensity

        for kind in ("pack", "unpack"):
            for nbits in NBITS_SWEEP:
                r = codec_roofline(kind, (20, 128, 128), nbits=nbits)
                assert r.bound == "memory"
                assert r.intensity < ridge_intensity() / 100
                assert r.memory_s > r.compute_s
                assert r.as_dict()["nbits"] == nbits

    def test_rejects_unknown_kind(self):
        from repro.roofline import codec_roofline

        with pytest.raises(ValueError, match="pack"):
            codec_roofline("transcode", (1, 8, 128))


# ---------------------------------------------------------------------------
# IOStats effective-byte accounting
# ---------------------------------------------------------------------------

class TestEffectiveBytes:
    def test_record_snapshot_reset(self):
        s = IOStats("codec")
        s.record("codec_pack", nbytes_w=100, effective_w=400)
        s.record("codec_unpack", nbytes_r=50, effective_r=200)
        snap = s.snapshot()
        assert snap["effective_bytes_written"] == 400
        assert snap["effective_bytes_read"] == 200
        assert snap["bytes_written"] == 100
        s.reset()
        assert s.snapshot()["effective_bytes_written"] == 0

    def test_merge_and_burst(self):
        a, b = IOStats("a"), IOStats("b")
        a.record_burst([("codec_pack", {"effective_w": 10}),
                        ("codec_pack", {"effective_w": 5, "count": 2})])
        b.record("codec_unpack", effective_r=7)
        m = IOStats.merged([a, b])
        assert m.effective_bytes_written == 15
        assert m.effective_bytes_read == 7
        assert m.ops["codec_pack"] == 3


# ---------------------------------------------------------------------------
# the hammer's codec cells (acceptance: effective >= 1.5x wire at 16 bits)
# ---------------------------------------------------------------------------

class TestHammerCodec:
    @pytest.fixture()
    def hammer(self):
        import sys
        from pathlib import Path

        sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmarks"))
        import fdb_hammer

        return fdb_hammer

    def test_scaling_sweep_reports_codec_cells(self, hammer, tmp_path):
        spec = hammer.HammerSpec(n_steps=2, n_params=2, n_levels=2, field_size=1 << 13)
        out = str(tmp_path / "BENCH_contention.json")
        res = hammer.scaling_sweep(
            spec, backends=("posix",), procs_list=(1, 2), out=out, codec_nbits=16
        )
        assert set(res["backends"]) == {"posix", "posix+codec16"}
        with open(out) as f:
            bench = json.load(f)
        rows = bench["backends"]["posix+codec16"]["sweep"]
        for row in rows:
            for phase in ("write", "read"):
                r = row[phase]
                assert r["effective_GiBps"] >= 1.5 * r["wire_GiBps"]
                assert r["codec_ratio"] >= 1.5
        # raw cells stay exactly as before — no codec keys
        assert "codec_ratio" not in bench["backends"]["posix"]["sweep"][0]["write"]

    def test_archive_packs_one_launch_per_step_batch(self, hammer, tmp_path):
        spec = hammer.HammerSpec(
            n_procs=2, n_steps=3, n_params=2, n_levels=2,
            field_size=1 << 13, codec_nbits=16,
        )
        fdb = hammer.make_backend("posix", root=str(tmp_path), codec_nbits=16)
        try:
            tr = Tracer()
            install_tracer(fdb, tr)
            hammer.run_hammer(fdb, spec, "archive")
            # one grib_pack launch per (proc, output step) batch — never per field
            assert launches(tr)["pack"] == spec.n_procs * spec.n_steps
            w = hammer.run_hammer(fdb, spec, "archive")
        finally:
            fdb.close()
        assert w["codec_ratio"] >= 1.5
        assert w["effective_GiBps"] >= 1.5 * w["wire_GiBps"]

    def test_tiered_codec_config_round_trips(self, hammer):
        spec = hammer.HammerSpec(
            n_procs=2, n_steps=2, n_params=2, n_levels=2,
            field_size=1 << 13, codec_nbits=16,
        )
        rows = hammer.run_config(
            hammer.load_config("tiered-codec"), spec, io_modes=("batched",)
        )
        row = rows[0]
        assert row["effective_bytes_written"] == spec.total_bytes
        assert row["wire_bytes_written"] > 0
        assert row["codec_ratio_w"] > 1.0  # hot 16-bit tier wins, cold 24 rides uint32


# ---------------------------------------------------------------------------
# streamed replies: the whole set decodes part by part as the parts land
# ---------------------------------------------------------------------------

#: a max_frame that holds one to three fields of a 16x128 grid
SMALL_FRAME = 16 << 10


@pytest.fixture
def streamed_tiers(tmp_path):
    """The benchmark's tiered tree at a small max_frame: member 0 on a
    16-bit DAOS tier, every other member on a 24-bit POSIX tier, each
    behind its own server."""
    from repro.core import FDBServer, RemoteFDB

    def tier(inner, nbits):
        server = FDBServer(inner, owns_fdb=True, max_frame=SMALL_FRAME)
        server.start()
        return CodecFDB(RemoteFDB(server=server, max_frame=SMALL_FRAME), nbits=nbits)

    fdb = SelectFDB(
        [("number=0", tier(make_fdb("daos", schema=NWP_SCHEMA_DAOS, engine=DaosEngine()), 16))],
        default=tier(make_fdb("posix", schema=NWP_SCHEMA_POSIX, root=str(tmp_path / "cold")), 24),
    )
    yield fdb
    fdb.close()


def archive_column(fdb, member: str, nbits: int, n: int = 24, seed: int = 0):
    """``n`` fields of one member archived one a call; their keys, source
    fields and the key->bytes reference of their payloads."""
    keys = [example_key(number=member, step=str(s)) for s in range(n)]
    fields = temperature_fields(np.random.default_rng(seed), n, 16, 128)
    for k, f in zip(keys, fields):
        fdb.archive_fields([k], f[None])
    fdb.flush()
    ref = {k: encode_fields(f[None], nbits=nbits)[0] for k, f in zip(keys, fields)}
    return keys, fields, ref


def column_request(keys) -> dict:
    return {**dict(keys[0]), "number": sorted({k["number"] for k in keys}),
            "step": sorted({k["step"] for k in keys}, key=int)}


@pytest.mark.parametrize("member,nbits", [("0", 16), ("5", 24)])
class TestStreamedDecode:
    def test_parts_decode_as_they_land_into_one_array(self, streamed_tiers, member, nbits):
        from repro.core.remote import protocol as P

        fdb = streamed_tiers
        keys, fields, ref = archive_column(fdb, member, nbits)
        # byte for byte before decoding: the streamed parts, the tier's
        # in-process store and the key->bytes reference agree
        streamed = [h.read() for _, hs, _ in fdb.retrieve_batch_parts(keys) for h in hs]
        tier = fdb.route(keys[0]).inner
        assert streamed == [ref[k] for k in keys] == [tier._server.fdb.read(k) for k in keys]

        tr = Tracer()
        install_tracer(fdb, tr)
        got = fdb.retrieve_fields(column_request(keys))
        arr = got.arrays()
        wire = [s for s in tr.spans() if s.name == "wire.retrieve_batch"]
        parts = len(P.split_reply(P.Op.RETRIEVE_BATCH, [(k, len(p)) for k, p in ref.items()],
                                  P.part_bound(SMALL_FRAME)))
        assert len(wire) == 1 and wire[0].attrs["parts"] == parts >= 8
        # one unpack launch a part, into one array that arrays() hands back
        assert launches(tr)["unpack"] == parts
        assert got.arrays() is arr and arr.shape == fields.shape
        # the decoded arrays are the reference decode of the stored codes
        codes = np.stack([
            np.frombuffer(p, dtype=payload_dtype(nbits), offset=CODEC_HEADER_SIZE)
            .reshape(16, 128).astype(np.int32) for p in ref.values()])
        hdrs = [parse_header(p) for p in ref.values()]
        expected = np.asarray(unpack_ref(
            jnp.asarray(codes), jnp.asarray([h.ref for h in hdrs], jnp.float32),
            jnp.asarray([h.scale for h in hdrs], jnp.float32)))
        assert np.abs(arr - expected).max() <= 2 * np.spacing(np.float32(np.abs(expected).max()))
        assert np.array_equal(arr, np.stack(decode_payloads(list(ref.values()))))
        # the receiving side holds at most two parts at once
        per_field = len(next(iter(ref.values())))
        most = max(len(r) for r in P.split_reply(
            P.Op.RETRIEVE_BATCH, [(k, len(p)) for k, p in ref.items()], P.part_bound(SMALL_FRAME)))
        assert 0 < got.buffered_max <= 2 * most * per_field
        counters = fdb.stats_snapshot()["counters"]
        assert counters["unpack_part_buffered_max"] == got.buffered_max

    def test_an_error_among_the_parts_raises(self, streamed_tiers, member, nbits, monkeypatch):
        import repro.core.remote.server as server_mod
        from repro.core import RemoteError

        fdb = streamed_tiers
        keys, _, _ = archive_column(fdb, member, nbits)
        read_items = server_mod._read_items
        calls = []

        def failing_third_part(handles):
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("store failed mid reply")
            return read_items(handles)

        monkeypatch.setattr(server_mod, "_read_items", failing_third_part)
        with pytest.raises(RemoteError, match="store failed mid reply"):
            fdb.retrieve_fields(column_request(keys)).arrays()

    def test_a_decoded_set_is_not_fetched_again(self, streamed_tiers, member, nbits):
        fdb = streamed_tiers
        keys, fields, _ = archive_column(fdb, member, nbits)
        server = fdb.route(keys[0]).inner._server

        def served() -> int:
            return server.wire_stats.snapshot()["ops"].get("wire_retrieve_batch", 0)

        before = served()
        got = fdb.retrieve_fields(column_request(keys))
        arr = got.arrays()
        assert got.arrays() is arr
        assert got.missing() == []
        assert all(a.base is arr for a in got.read_all().values())
        assert dict(got.items()).keys() == set(keys)
        assert served() - before == 1
        # the parts went by: the FieldSet keeps no handle of them
        assert repr(got._fs) == f"FieldSet({len(keys)} fields, 0 resolved)"

    def test_a_set_decoded_in_part_decodes_the_rest_once(self, streamed_tiers, member, nbits):
        fdb = streamed_tiers
        keys, fields, ref = archive_column(fdb, member, nbits)
        server = fdb.route(keys[0]).inner._server
        before = server.wire_stats.snapshot()["ops"].get("wire_retrieve_batch", 0)
        tr = Tracer()
        install_tracer(fdb, tr)
        got = DecodedFieldSet(fdb.retrieve_many(column_request(keys)), chunk=5, tracer=tr)
        first = got[keys[0]]
        arr = got.arrays()
        assert arr[0] is not first and np.array_equal(arr[0], first)
        assert np.array_equal(arr, np.stack(decode_payloads([ref[k] for k in keys])))
        assert got.missing() == []
        # the FieldSet's first chunk of 64 keys fetched the whole column
        # once; the decoder's first chunk of 5, then the rest, in a launch
        # each, from those handles
        assert server.wire_stats.snapshot()["ops"]["wire_retrieve_batch"] - before == 1
        assert launches(tr)["unpack"] == 2


@pytest.mark.parametrize("member,nbits", [("0", 16), ("5", 24)])
@pytest.mark.parametrize("n,feeds", [(1, 0), (24, 1)])
def test_only_a_reply_that_goes_on_takes_a_thread(streamed_tiers, member, nbits, n, feeds,
                                                  monkeypatch):
    """A one-frame reply decodes on the caller's thread alone; the parts of
    a streamed reply after the first are taken on one thread besides."""
    import repro.core.codec as codec_mod

    made = []
    part_feed = codec_mod._PartFeed

    def counted(*args):
        made.append(1)
        return part_feed(*args)

    monkeypatch.setattr(codec_mod, "_PartFeed", counted)
    fdb = streamed_tiers
    keys, fields, ref = archive_column(fdb, member, nbits, n=n)
    got = fdb.retrieve_fields(column_request(keys))
    assert np.array_equal(got.arrays(), np.stack(decode_payloads([ref[k] for k in keys])))
    assert len(made) == feeds
    assert (got.buffered_max > 0) == bool(feeds)


def test_a_request_across_both_tiers_decodes_in_key_order(streamed_tiers):
    fdb = streamed_tiers
    hot_keys, hot, _ = archive_column(fdb, "0", 16, n=10, seed=1)
    cold_keys, cold, _ = archive_column(fdb, "5", 24, n=10, seed=2)
    got = fdb.retrieve_fields(column_request(hot_keys + cold_keys))
    arr = got.arrays()
    order = {k: i for i, k in enumerate(got.keys)}
    for keys, fields, nbits in ((hot_keys, hot, 16), (cold_keys, cold, 24)):
        for k, f in zip(keys, fields):
            # a quantisation step, or float32's resolution near the
            # values where 24-bit steps are finer
            step = (f.max() - f.min()) / ((1 << nbits) - 1)
            assert np.abs(arr[order[k]] - f).max() <= max(step, 4 * np.spacing(np.abs(f).max()))


def test_concurrent_streamed_reads_stay_whole(streamed_tiers):
    """More readers than cores, each taking streamed whole-set reads with a
    short switch interval: every answer is whole and right, and no reader
    holds more than two parts."""
    import os
    import threading

    from repro.core.remote import protocol as P

    fdb = streamed_tiers
    keys, fields, ref = archive_column(fdb, "0", 16, n=12, seed=3)
    expected = np.stack(decode_payloads(list(ref.values())))
    part = max(len(r) for r in P.split_reply(
        P.Op.RETRIEVE_BATCH, [(k, len(p)) for k, p in ref.items()], P.part_bound(SMALL_FRAME)))
    field = len(next(iter(ref.values())))
    errors: list = []

    def reader():
        try:
            for _ in range(3):
                got = fdb.retrieve_fields(column_request(keys))
                assert np.array_equal(got.arrays(), expected)
                assert got.buffered_max <= 2 * part * field
        except BaseException as e:  # noqa: BLE001 -- reported below
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range((os.cpu_count() or 2) + 2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
