"""Seconds of socket work on the wire per effective GB archived: the
``wire.send`` and ``wire.recv`` spans under ``wire.archive_batch``, over
the float32 bytes writers archived in the window."""

from fdbbench.splits import wire_io_s_per_gb


def read(ctx):
    return wire_io_s_per_gb(ctx.spans, "wire.archive_batch", ctx.effective_bytes("archive"))
