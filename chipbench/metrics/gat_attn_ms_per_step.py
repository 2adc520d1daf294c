"""Device time of GAT's attention scores and softmax, per training step:
the union of the ops under the named scopes `gat/l<i>/scores` (source and
destination scores, LeakyReLU, mask) and `gat/l<i>/softmax` of
`models/gnn/models.py::gat_layer`, forward and backward, in the traced
window (`scopes.py`). JAX names a scope's ops `jvp(<scope>)` in the
forward of a gradient and `transpose(jvp(<scope>))` in its backward,
with the whole scope path inside the parentheses, so each form is a
pattern of its own. A fusion that joins ops of several scopes carries
the name of one of them. None where the trace has no such scopes (a
model other than GAT, or a program without them)."""
from chipbench import scopes

SCOPES = ("gat/l*/scores", "jvp(gat/l*/scores)",
          "transpose(jvp(gat/l*/scores))",
          "gat/l*/softmax", "jvp(gat/l*/softmax)",
          "transpose(jvp(gat/l*/softmax))")


def read(ctx):
    ns = scopes.scope_ns(ctx, SCOPES)
    if ns <= 0 or ctx.steps <= 0:
        return None
    return ns / 1e6 / ctx.steps
