"""The Pallas AirComp kernels' device time as a share of the traced window,
in percent (averaged over the chips used)."""

KERNEL = "aircomp"


def read(ctx):
    if not ctx.complete:
        return None
    device_s = ctx.trace.kernel_s(KERNEL)
    if device_s <= 0 or ctx.trace.window_s <= 0:
        return None
    return 100.0 * device_s / ctx.trace.window_s
