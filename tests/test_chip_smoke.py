"""The chip smoke's control flow, on the CPU at a tiny grid.

``chip_smoke.run_smoke`` is the whole body of the chip bring-up run (build
the served tiered tree, archive, query, compare); here the kernels run in
interpret mode.  ``main()`` alone insists on a TPU.
"""

import json
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402

TINY_GRID = (16, 256)


def test_smoke_body_archives_queries_and_checks():
    res = chip_smoke.run_smoke(TINY_GRID, seed=3)
    assert res["fields_archived"] == 128
    assert res["fields_listed"] == 128
    assert res["fields_retrieved"] == 65
    # hot tier halves the bytes, cold 24-bit codes ride 4-byte containers;
    # 32-byte headers keep the tiny grid a little under 4/3
    assert 1.25 < res["effective_over_wire"] < 4 / 3


def test_smoke_compiles_every_launch_shape():
    _, texts = chip_smoke.compile_codec(TINY_GRID)
    assert set(texts) == {"grib_pack/16", "grib_pack/24", "grib_unpack/32", "grib_unpack/1"}


def test_check_rejects_a_field_off_by_one_step():
    src = chip_smoke.step_fields(1, 0, TINY_GRID, seed=0)[:2]
    step = (src[0].max() - src[0].min()) / ((1 << 16) - 1)
    bad = src.copy()
    bad[0, 3, 5] += step
    with pytest.raises(AssertionError, match="field 0"):
        chip_smoke.check_decoded(bad, src, 16)


def test_main_refuses_without_tpu(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    assert capsys.readouterr().out == ""


def test_main_refuses_interpreted_kernels(monkeypatch, capsys):
    """A device that reports itself as a TPU while the kernels compile for
    the interpreter must not pass the smoke."""
    import repro.compile_cache

    fake = types.SimpleNamespace(platform="tpu", device_kind="fake", memory_stats=dict)
    monkeypatch.setattr(chip_smoke.jax, "devices", lambda: [fake])
    monkeypatch.setattr(repro.compile_cache, "use_compile_cache", lambda: "unset")
    monkeypatch.setattr(chip_smoke, "GLOBAL_GRID", TINY_GRID)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert "not compiled as a TPU kernel" in out.err
    for line in out.out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
