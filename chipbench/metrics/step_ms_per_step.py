"""Device time of the train-step program (`train/gnn_loop.py`'s jitted
`train_step`: the model's forward and backward, and AdamW) in the traced
window, per training step."""

MODULE = "jit_train_step"


def read(ctx):
    ns = ctx.module_ns(MODULE)
    if ns <= 0 or ctx.steps <= 0:
        return None
    return ns / 1e6 / ctx.steps
