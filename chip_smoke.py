"""Bring-up smoke: the tiered, codec-on-device FDB on one TPU chip.

Drives the main path once, end to end, through the public API:

1. compile ``grib_pack`` and ``grib_unpack`` at the shapes the run uses and
   insist that each is a Mosaic kernel (``tpu_custom_call``), never the
   Pallas interpreter;
2. build the tiered deployment with ``build_fdb`` -- ensemble member 0 on
   the hot tier (16-bit codec over DAOS), everything else on the cold tier
   (24-bit codec over POSIX) -- each tier served behind the wire protocol
   in this process, the codec on the client side of it;
3. archive 2 members x 2 steps x 4 params x 8 levels of the 0.1-degree
   regular lat-lon grid (MARS ``grid=0.1/0.1``: 1801 x 3600 float32, about
   25.9 MB a field, 3.3 GB in all), one ``archive_fields`` call of 32 fields
   and one ``flush()`` per output step of a member;
4. retrieve a partial request across both tiers, one exact key, and list
   everything; check counts, the quantisation bound against the source
   fields and agreement with ``unpack_ref(pack_ref(...))``.

Run from the root of a checkout::

    python chip_smoke.py [--seed N]

It exits non-zero when JAX finds no TPU, when a kernel is not compiled for
the chip, when a check fails or when any phase raises.  The last line of
its output is ``{"ok": true, "device": {...}}``; the lines before it are
set-up and wall times of this one run, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import Key, build_fdb  # noqa: E402
from repro.fields import synthetic_field  # noqa: E402
from repro.kernels.grib_pack import grib_pack, grib_unpack  # noqa: E402
from repro.kernels.grib_pack.ref import field_stats, pack_ref, unpack_ref  # noqa: E402

#: MARS ``grid=0.1/0.1`` regular lat-lon, poles included
GLOBAL_GRID = (1801, 3600)
PARAMS = ("t", "u", "v", "q")
LEVELS = tuple(range(1, 9))
MEMBERS = (0, 1)
STEPS = (0, 6)
HOT_NBITS, COLD_NBITS = 16, 24
DATASET = {
    "class": "od", "stream": "enfo", "expver": "0001", "date": "20240601",
    "time": "0000", "type": "pf", "levtype": "ml",
}
#: one output step of every member, all params and levels: both tiers
PARTIAL_REQUEST = {**DATASET, "step": "0", "param": "*", "levelist": "1/to/8"}
EXACT_KEY = Key({**DATASET, "number": "1", "step": "6", "param": "q", "levelist": "8"})
FIELDS_PER_STEP = len(PARAMS) * len(LEVELS)


def tiered_config(posix_root: str) -> dict:
    """The hammer's ``tiered-codec`` deployment with each tier's store
    served over the wire in-process: codec nodes stay on the client side,
    so one process packs and unpacks on the device."""
    def tier(nbits: int, local: dict) -> dict:
        return {"type": "codec", "nbits": nbits, "inner": {"type": "remote", "inner": local}}

    return {
        "type": "select",
        "rules": [{
            "match": "number=0",
            "fdb": tier(HOT_NBITS, {"backend": "daos", "schema": "nwp-daos"}),
        }],
        "default": tier(COLD_NBITS, {"backend": "posix", "schema": "nwp-posix",
                                     "root": posix_root}),
    }


def nbits_of(member: int) -> int:
    return HOT_NBITS if member == 0 else COLD_NBITS


def step_keys(member: int, step: int) -> list[Key]:
    """The 32 field keys of one member's output step, params major."""
    return [Key({**DATASET, "number": str(member), "step": str(step),
                 "param": param, "levelist": str(level)})
            for param in PARAMS for level in LEVELS]


def step_fields(member: int, step: int, grid: tuple[int, int], seed: int) -> np.ndarray:
    """The ``(32, H, W)`` source fields of :func:`step_keys`, same order."""
    return np.stack([
        synthetic_field(param, member, step, level=level, seed=seed,
                        nlat=grid[0], nlon=grid[1])
        for param in PARAMS for level in LEVELS
    ])


def compile_codec(grid: tuple[int, int]) -> tuple[float, dict[str, str]]:
    """Lower and compile the codec at every shape the smoke launches; return
    the seconds taken and each compiled program's text."""
    h, w = grid
    texts = {}
    t0 = time.perf_counter()
    for nbits in (HOT_NBITS, COLD_NBITS):
        x = jax.ShapeDtypeStruct((FIELDS_PER_STEP, h, w), jnp.float32)
        texts[f"grib_pack/{nbits}"] = grib_pack.lower(x, nbits=nbits).compile().as_text()
    for f in (FIELDS_PER_STEP, 1):
        codes = jax.ShapeDtypeStruct((f, h, w), jnp.int32)
        vec = jax.ShapeDtypeStruct((f,), jnp.float32)
        texts[f"grib_unpack/{f}"] = grib_unpack.lower(codes, vec, vec).compile().as_text()
    return time.perf_counter() - t0, texts


def _reference(fields: np.ndarray, nbits: int) -> np.ndarray:
    x = jnp.asarray(fields)
    ref, scale, inv_scale = field_stats(x, nbits)
    return np.asarray(unpack_ref(pack_ref(x, ref, inv_scale, nbits), ref, scale).block_until_ready())


def check_decoded(decoded: np.ndarray, source: np.ndarray, nbits: int) -> None:
    """Each decoded field lies within half a quantisation step (plus float32
    rounding) of its source and matches the pure-jnp codec reference to
    float32's resolution of the quantiser.

    The reference computes ``scale`` in another XLA program than the codec.
    On the TPU the two may round that division an ulp apart, which moves a
    code by up to ``2**nbits * 2 * eps`` (three codes at 24 bits), plus one
    where the product sits at a rounding boundary."""
    if decoded.shape != source.shape:
        raise AssertionError(f"decoded {decoded.shape} != source {source.shape}")
    eps = float(np.finfo(np.float32).eps)
    codes_apart = 1 + (1 << nbits) * 2 * eps
    expect = _reference(source, nbits)
    for i in range(source.shape[0]):
        src, dec = source[i], decoded[i]
        mag = float(np.abs(src).max())
        step = float(src.max() - src.min()) / ((1 << nbits) - 1)
        err = float(np.abs(dec - src).max())
        if not err <= 0.5 * step + 4 * eps * mag:
            raise AssertionError(
                f"field {i} ({nbits}-bit): error {err} exceeds half a step {0.5 * step}")
        gap = float(np.abs(dec - expect[i]).max())
        if not gap <= codes_apart * step + 2 * eps * mag:
            raise AssertionError(
                f"field {i} ({nbits}-bit): differs from unpack_ref(pack_ref()) by {gap}")


def run_smoke(grid: tuple[int, int], seed: int = 0) -> dict:
    """Build the served tiered tree, archive, query and check; return the
    counts, wall seconds and byte totals of the run.  Raises on any failed
    check."""
    sources: dict[tuple[int, int], np.ndarray] = {}
    out: dict = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_posix_") as root, \
            build_fdb(tiered_config(root)) as fdb:
        t0 = time.perf_counter()
        for member in MEMBERS:
            for step in STEPS:
                sources[member, step] = step_fields(member, step, grid, seed)
        out["generate_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        for (member, step), fields in sources.items():
            fdb.archive_fields(step_keys(member, step), fields)
            fdb.flush()
        out["archive_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        partial = fdb.retrieve_fields(PARTIAL_REQUEST)
        got = partial.arrays()
        exact = fdb.retrieve_fields(EXACT_KEY).arrays()
        out["retrieve_s"] = time.perf_counter() - t0

        listed = list(fdb.list())
        snap = fdb.stats_snapshot()

    n_expect = len(MEMBERS) * FIELDS_PER_STEP
    if len(partial) != n_expect or got.shape != (n_expect, *grid):
        raise AssertionError(f"partial request gave {got.shape}, expected {n_expect} fields")
    if exact.shape != (1, *grid):
        raise AssertionError(f"exact-key retrieve gave {exact.shape}")
    n_archived = len(MEMBERS) * len(STEPS) * FIELDS_PER_STEP
    if len(listed) != n_archived:
        raise AssertionError(f"list() gave {len(listed)} entries, archived {n_archived}")

    index = {k: i for i, k in enumerate(partial.keys)}
    for member in MEMBERS:
        rows = [index[k] for k in step_keys(member, 0)]
        check_decoded(got[rows], sources[member, 0], nbits_of(member))
    i = step_keys(1, 6).index(EXACT_KEY)
    check_decoded(exact, sources[1, 6][i:i + 1], COLD_NBITS)

    out.update(
        fields_archived=n_archived,
        fields_retrieved=len(partial) + 1,
        fields_listed=len(listed),
        effective_bytes_written=snap["effective_bytes_written"],
        wire_bytes_written=snap["bytes_written"],
        effective_over_wire=snap["effective_bytes_written"] / snap["bytes_written"],
    )
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of the synthetic fields")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 1
    from repro.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    print(f"device: {dev.device_kind} x {len(jax.devices())}")
    compile_s, texts = compile_codec(GLOBAL_GRID)
    for name, text in texts.items():
        if "tpu_custom_call" not in text:
            print(f"chip_smoke: {name} is not compiled as a TPU kernel", file=sys.stderr)
            return 1
    print(f"compile_s: {compile_s} ({', '.join(texts)})")

    res = run_smoke(GLOBAL_GRID, seed=args.seed)
    for k, v in res.items():
        print(f"{k}: {v}")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
