"""Device time of each hop's neighbor sampling (`sampler.sample`; the first
hop also holds the build's key split and a shared sampler's epoch state),
under the named scopes `build/hop<h>/sample` of `core/minibatch.py`'s batch
build, in the traced window, per training step (`scopes.py`: the union of
the ops' intervals)."""
from chipbench import scopes

SCOPES = ("build/hop*/sample",)


def read(ctx):
    ns = scopes.scope_ns(ctx, SCOPES)
    if ns <= 0 or ctx.steps <= 0:
        return None
    return ns / 1e6 / ctx.steps
