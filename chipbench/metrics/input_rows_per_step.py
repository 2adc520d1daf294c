"""Mean unique input-level rows per batch (`MiniBatch.num_unique`, the
rows layer 0 gathers) over the traced window's steps."""


def read(ctx):
    if not ctx.counts:
        return None
    return sum(c["n"][-1] for c in ctx.counts) / len(ctx.counts)
