"""Milliseconds a pack launch spends in ``codec.pack.device`` beyond the
device time of ``jit_grib_pack``, averaged over the launches of the
window: host-to-device copy, dispatch, the int32 copy back and waits."""

from fdbbench.splits import launch_ms


def read(ctx):
    return launch_ms(ctx, "codec.pack.device", "jit_grib_pack")
