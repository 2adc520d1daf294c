"""Device time of the batch-build program (`core/minibatch.py`'s jitted
`_build_batch`: sampling, dedup, position maps) in the traced window,
per training step."""

MODULE = "_build_batch"


def read(ctx):
    ns = ctx.module_ns(MODULE)
    if ns <= 0 or ctx.steps <= 0:
        return None
    return ns / 1e6 / ctx.steps
