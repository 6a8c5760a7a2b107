"""Seconds of socket work on the wire per effective GB retrieved: the
``wire.send`` and ``wire.recv`` spans under ``wire.retrieve_*``, over the
float32 bytes readers decoded in the window."""

from fdbbench.splits import wire_io_s_per_gb


def read(ctx):
    return wire_io_s_per_gb(ctx.spans, "wire.retrieve", ctx.effective_bytes("retrieve"))
