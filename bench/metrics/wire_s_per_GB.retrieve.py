"""Seconds on the wire per effective GB retrieved: the self time of the
``wire.retrieve_*`` spans, less their ``server.*`` children, over the
float32 bytes readers decoded in the window."""

from fdbbench.spans import wire_s_per_gb


def read(ctx):
    return wire_s_per_gb(ctx.spans, "wire.retrieve", ctx.effective_bytes("retrieve"))
