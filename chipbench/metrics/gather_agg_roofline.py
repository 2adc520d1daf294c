"""Share of its roofline that the fused gather-aggregate kernels
(`kernels/gather_agg`: forward, dx, and dw where the model needs it)
reach in the traced window: the least time of the window's calls
(`rooflines/gather_agg.py`, from each batch's real counts) over the
kernels' summed device time."""


def read(ctx):
    roof = ctx.layout.module("rooflines", "gather_agg")
    ns = ctx.kernel_ns(roof.KERNELS)
    if ns <= 0 or not ctx.counts:
        return None
    least = sum(roof.least_time_s(ctx.flops.gather_agg_calls(ctx.cfg, c),
                                  ctx.peaks) for c in ctx.counts)
    return 100.0 * least / (ns / 1e9)
