"""Host seconds of the codec's pack step outside the device call, per
effective GB packed: the ``codec.pack.stack`` (stacking the batch) and
``codec.pack.frame`` (narrowing cast and framing) spans, over the float32
bytes of their ``codec.pack`` parents."""

from fdbbench.splits import child_s_per_gb


def read(ctx):
    return child_s_per_gb(ctx.spans, "codec.pack", ("codec.pack.stack", "codec.pack.frame"))
