"""Host seconds in the codec's pack step per effective GB packed: the
summed ``codec.pack`` spans (stack, device call, copies, cast, framing)
over the float32 bytes they carry."""

from fdbbench.spans import codec_s_per_gb


def read(ctx):
    return codec_s_per_gb(ctx.spans, "codec.pack")
