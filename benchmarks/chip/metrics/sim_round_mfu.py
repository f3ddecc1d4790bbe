"""Model FLOPs of the traced window (from ``costs/``, as the driver counts
the work it did) per second of the window, over the chips' bfloat16 peak,
in percent."""


def read(ctx):
    flops = ctx.work.get("model_flops")
    if not flops or ctx.trace.window_s <= 0:
        return None
    chips = len(ctx.trace.ops)
    return 100.0 * flops / ctx.trace.window_s / (chips * ctx.peaks["bf16_flops"])
