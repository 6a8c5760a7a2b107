"""Faults planted in the timed path, which the comparison has to catch.

Used by ``bench/calibrate.py`` on the chip and by the CPU tests; the
benchmark's own runs never plant one.
"""

from __future__ import annotations

import contextlib
import copy

__all__ = ["altered_answers", "stale_reads", "swapped_widths"]


@contextlib.contextmanager
def altered_answers(steps: float = 64.0):
    """Every unpack launch returns its first field's first row moved by
    ``steps`` quantisation steps: an answer altered where it is produced."""
    from repro.core import codec

    original = codec.grib_unpack

    def unpack(codes, ref, scale, **kw):
        out = original(codes, ref, scale, **kw)
        return out.at[0, 0].add(steps * scale[0])

    codec.grib_unpack = unpack
    try:
        yield
    finally:
        codec.grib_unpack = original


class _Relabelled:
    """An answer whose keys claim another step than the fields hold."""

    def __init__(self, answer, step: str):
        self._answer, self._step = answer, step

    @property
    def keys(self) -> list[dict]:
        return [{**dict(k), "step": self._step} for k in self._answer.keys]

    def arrays(self):
        return self._answer.arrays()


@contextlib.contextmanager
def stale_reads(*, relabel: bool):
    """Every request into a step after a cycle's first is answered from the
    step before it: a stale answer.  ``relabel`` gives it the keys that
    were asked for, as a cache that serves an old object under a new key;
    without it the answer keeps the keys of the step it came from."""
    from repro.core.client import FDBClient

    original = FDBClient.retrieve_fields

    def retrieve(self, request):
        step = request.get("step")
        if step is None or int(step) == 0:
            return original(self, request)
        answer = original(self, {**request, "step": str(int(step) - 1)})
        return _Relabelled(answer, step) if relabel else answer

    FDBClient.retrieve_fields = retrieve
    try:
        yield
    finally:
        FDBClient.retrieve_fields = original


def swapped_widths(config: dict) -> dict:
    """The configuration with every codec tier packing at another width
    than it states (16 and 24 bits trade places; others go to 16): fields
    land on a tier of the wrong width."""
    config = copy.deepcopy(config)

    def walk(node):
        if isinstance(node, dict):
            if node.get("type") == "codec":
                node["nbits"] = {16: 24, 24: 16}.get(node["nbits"], 16)
            for v in node.values():
                walk(v)
        elif isinstance(node, list):
            for v in node:
                walk(v)

    walk(config["tree"])
    return config
