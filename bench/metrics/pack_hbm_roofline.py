"""Share of the HBM roofline reached by the jitted ``grib_pack`` program:
the least bytes packing needs (each float32 read once, each code written
once at its container width) at the chip's peak HBM rate, over the summed
device time of every operation of that program (statistics pass, relayout
copies and the kernel) in the traced window."""

from fdbbench.roofline import kernel_share_pct, pack_bytes


def read(ctx):
    return kernel_share_pct(ctx, "jit_grib_pack", "codec.pack", pack_bytes)
