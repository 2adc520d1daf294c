"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device's op intervals over the window, averaged
over the chips in use."""


def read(ctx):
    if ctx.window_ns <= 0 or ctx.busy_ns <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_ns / ctx.window_ns)
