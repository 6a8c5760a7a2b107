"""Host seconds in the codec's unpack step per effective GB decoded: the
summed ``codec.unpack`` spans over the float32 bytes they produce."""

from fdbbench.spans import codec_s_per_gb


def read(ctx):
    return codec_s_per_gb(ctx.spans, "codec.unpack")
