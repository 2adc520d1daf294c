"""Device time of the fused gather-aggregate kernels (`kernels/gather_agg`:
forward, dx, and dw where the model needs it), under their named scopes
`gather_agg_fwd`, `gather_agg_dx` and `gather_agg_dw`, in the traced
window, per training step (`scopes.py`)."""
from chipbench import scopes

SCOPES = ("gather_agg_fwd", "gather_agg_dx", "gather_agg_dw")


def read(ctx):
    ns = scopes.scope_ns(ctx, SCOPES)
    if ns <= 0 or ctx.steps <= 0:
        return None
    return ns / 1e6 / ctx.steps
