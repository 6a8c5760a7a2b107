"""Synthetic NWP field generator invariants."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from repro.fields import synthetic_field
from repro.kernels.grib_pack import pack_to_bytes, unpack_from_bytes


def test_deterministic_and_distinct():
    a = synthetic_field("2t", member=1, step=3)
    b = synthetic_field("2t", member=1, step=3)
    c = synthetic_field("2t", member=2, step=3)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_physical_ranges():
    t = synthetic_field("2t")
    assert 180 < t.mean() < 340           # Kelvin-ish
    p = synthetic_field("msl")
    assert 9e4 < p.mean() < 1.1e5          # Pa


def test_grib_roundtrip_on_synthetic():
    f = synthetic_field("10u", nlat=64, nlon=128)
    payload, meta = pack_to_bytes(f)
    back = unpack_from_bytes(payload, meta)
    quantum = (f.max() - f.min()) / 65535
    assert np.abs(back - f).max() <= quantum * 1.01


def test_seed_selects_a_different_field():
    a = synthetic_field("t", member=1, step=6, level=3, seed=0)
    np.testing.assert_array_equal(a, synthetic_field("t", member=1, step=6, level=3, seed=0))
    assert not np.array_equal(a, synthetic_field("t", member=1, step=6, level=3, seed=1))
    assert not np.array_equal(a, synthetic_field("t", member=1, step=6, level=4, seed=0))


def test_same_field_in_every_process():
    """String hashing is salted per process; the field must not be."""
    code = (
        "from repro.fields import synthetic_field; "
        "print(synthetic_field('2t', member=1, step=3, seed=5).tobytes().hex())"
    )
    src = str(Path(__file__).resolve().parent.parent / "src")
    outs = set()
    for hash_seed in ("1", "2"):
        env = {**os.environ, "PYTHONHASHSEED": hash_seed, "PYTHONPATH": src}
        out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                             capture_output=True, text=True).stdout.strip()
        outs.add(out)
    assert outs == {synthetic_field("2t", member=1, step=3, seed=5).tobytes().hex()}
