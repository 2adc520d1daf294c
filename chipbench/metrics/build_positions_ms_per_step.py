"""Device time of each hop's position maps (both `_positions` binary
searches into the new level, and the block masks), under the named scopes
`build/hop<h>/positions` of `core/minibatch.py`'s batch build, in the traced
window, per training step (`scopes.py`: the union of the ops'
intervals)."""
from chipbench import scopes

SCOPES = ("build/hop*/positions",)


def read(ctx):
    ns = scopes.scope_ns(ctx, SCOPES)
    if ns <= 0 or ctx.steps <= 0:
        return None
    return ns / 1e6 / ctx.steps
