"""Server seconds per effective GB retrieved spent on the parts of streamed
replies: the ``server.retrieve_*.part`` spans (each part's store reads,
framing, and its write until the transport drained), over the float32
bytes readers decoded in the window.  A program that streams no reply has
no such span, and this reads nothing."""

from fdbbench.spans import duration, named


def read(ctx):
    parts = named(ctx.spans, lambda n: n.startswith("server.retrieve") and n.endswith(".part"))
    nbytes = ctx.effective_bytes("retrieve")
    if not parts or nbytes <= 0:
        return None
    return sum(duration(s) for s in parts) / (nbytes / 1e9)
