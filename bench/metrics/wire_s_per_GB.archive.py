"""Seconds on the wire per effective GB archived: the self time of the
``wire.archive_batch`` spans, less their ``server.*`` children, over the
float32 bytes writers archived in the window."""

from fdbbench.spans import wire_s_per_gb


def read(ctx):
    return wire_s_per_gb(ctx.spans, "wire.archive_batch", ctx.effective_bytes("archive"))
