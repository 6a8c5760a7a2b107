"""Seconds the decoder waits for parts per effective GB retrieved: the
``codec.unpack.wait`` spans (decoding waiting for a part that has not
landed yet), over the float32 bytes readers decoded in the window.  Near 0
the decoding sets the pace; a large value means the wire does.  A program
without these spans reads nothing."""

from fdbbench.spans import duration, named


def read(ctx):
    waits = named(ctx.spans, lambda n: n == "codec.unpack.wait")
    nbytes = ctx.effective_bytes("retrieve")
    if not waits or nbytes <= 0:
        return None
    return sum(duration(s) for s in waits) / (nbytes / 1e9)
