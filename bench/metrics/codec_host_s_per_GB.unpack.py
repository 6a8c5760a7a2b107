"""Host seconds of the codec's unpack step outside the device call, per
effective GB decoded: the ``codec.unpack.stack`` spans (codes widened to
int32 and stacked), over the float32 bytes of their ``codec.unpack``
parents."""

from fdbbench.splits import child_s_per_gb


def read(ctx):
    return child_s_per_gb(ctx.spans, "codec.unpack", ("codec.unpack.stack",))
