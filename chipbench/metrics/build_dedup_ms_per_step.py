"""Device time of each hop's dedup (the level and its sampled ids
concatenated, then `jnp.unique(size=cap)`), under the named scopes
`build/hop<h>/dedup` of `core/minibatch.py`'s batch build, in the traced
window, per training step (`scopes.py`: the union of the ops'
intervals)."""
from chipbench import scopes

SCOPES = ("build/hop*/dedup",)


def read(ctx):
    ns = scopes.scope_ns(ctx, SCOPES)
    if ns <= 0 or ctx.steps <= 0:
        return None
    return ns / 1e6 / ctx.steps
