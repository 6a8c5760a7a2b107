"""Milliseconds an unpack launch spends in ``codec.unpack.device`` beyond
the device time of ``jit_grib_unpack``, averaged over the launches of the
window: host-to-device copy, dispatch, the float32 copy back and waits."""

from fdbbench.splits import launch_ms


def read(ctx):
    return launch_ms(ctx, "codec.unpack.device", "jit_grib_unpack")
