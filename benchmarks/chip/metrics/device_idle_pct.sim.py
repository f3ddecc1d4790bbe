"""The device's idle share of the traced window, in percent: one minus the
union of its operations' intervals over the window (averaged over the
chips used)."""


def read(ctx):
    if not ctx.complete or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
