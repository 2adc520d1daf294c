"""Model FLOPs of the window's steps (the model's `flops/<model>.py`,
from each batch's real counts), over the traced window's wall time, over
the chip's bf16 peak."""


def read(ctx):
    if not ctx.counts or ctx.window_s <= 0:
        return None
    flops = sum(ctx.flops.step_flops(ctx.cfg, c) for c in ctx.counts)
    return 100.0 * flops / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
