"""Share of the HBM roofline reached by the jitted ``grib_unpack``
program: the least bytes unpacking needs (each code read once at its
container width, each float32 written once) at the chip's peak HBM rate,
over the summed device time of every operation of that program in the
traced window."""

from fdbbench.roofline import kernel_share_pct, unpack_bytes


def read(ctx):
    return kernel_share_pct(ctx, "jit_grib_unpack", "codec.unpack", unpack_bytes)
