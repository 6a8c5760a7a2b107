"""Seconds receiving the parts of streamed replies per effective GB
retrieved: the ``wire.recv_part`` spans (each part's header in hand to its
body complete), over the float32 bytes readers decoded in the window.  A
program that streams no reply has no such span, and this reads nothing."""

from fdbbench.spans import duration, named


def read(ctx):
    parts = named(ctx.spans, lambda n: n == "wire.recv_part")
    nbytes = ctx.effective_bytes("retrieve")
    if not parts or nbytes <= 0:
        return None
    return sum(duration(s) for s in parts) / (nbytes / 1e9)
