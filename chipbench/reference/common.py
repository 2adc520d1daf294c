"""What the plain references share: the batch as device arrays, dropout,
the node-classification loss, AdamW, the first training steps, and the
numbers that compare a candidate's first steps with the reference's.

Everything runs under `jax.default_matmul_precision("highest")` in the
dtype asked for: float32 for the reference, bfloat16 for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8
# a leaf whose reference gradient is below this share of the median
# leaf's moves under Adam by round-off alone: its change is not compared
STILL_LEAF = 1e-3


def device_batch(b: dict) -> dict:
    """Hop-order numpy batch -> the arrays the models read (the reference
    batch's other keys are dropped)."""
    return {"levels": [jnp.asarray(l, jnp.int32) for l in b["levels"]],
            "hops": [{k: jnp.asarray(v) for k, v in h.items()}
                     for h in b["hops"]],
            "labels": jnp.asarray(b["labels"], jnp.int32),
            "label_mask": jnp.asarray(b["label_mask"])}


def dropout(x, key, rate: float):
    """Inverted dropout with the keep mask `bernoulli(key, 1 - rate)`."""
    if rate <= 0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / jnp.asarray(keep, x.dtype), 0)


def softmax_ce(logits, labels, mask):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    m = mask.astype(jnp.float32)
    return ((lse - picked) * m).sum() / jnp.maximum(m.sum(), 1.0)


def adamw(params, grads, m, v, t: int, lr: float, wd: float):
    dt = jax.tree.leaves(params)[0].dtype
    c1 = 1.0 - B1 ** t
    c2 = 1.0 - B2 ** t

    def one(p, g, m, v):
        g = g.astype(dt)
        m = B1 * m + (1 - B1) * g
        v = B2 * v + (1 - B2) * g * g
        step = (m / c1) / (jnp.sqrt(v / c2) + EPS) + wd * p
        return (p - lr * step).astype(dt), m.astype(dt), v.astype(dt)

    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@functools.partial(jax.jit, static_argnames=("apply", "cfg_key", "dtype",
                                             "half_batch"))
def _loss_and_grad(params, batch, feats, dkey, *, apply, cfg_key, dtype,
                   half_batch):
    cfg = dict(cfg_key)
    label_mask = batch["label_mask"]
    if half_batch:
        # a fault: the second half of the sorted roots left out, the mean
        # taken over the rest
        n = label_mask.shape[0]
        label_mask = label_mask & (jnp.arange(n) < n // 2)

    def loss_fn(p):
        p = jax.tree.map(lambda x: x.astype(dtype), p)
        logits = apply(cfg, p, batch, feats.astype(dtype), dkey)
        return softmax_ce(logits, batch["labels"], label_mask)

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params)


def first_steps(model, cfg: dict, params0, batches, feats, dkeys,
                dtype=jnp.float32, half_batch: bool = False) -> dict:
    """Training steps of the reference module `model` from `params0`,
    one over each of `batches` (device batches). Returns the losses, the
    first gradient and the parameters after the last step, as host
    arrays."""
    cfg_key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                           for k, v in cfg.items()
                           if isinstance(v, (int, float, str, list))))
    p = jax.tree.map(lambda x: jnp.asarray(x).astype(dtype), params0)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, g1 = [], None
    for t, (b, k) in enumerate(zip(batches, dkeys), start=1):
        loss, g = _loss_and_grad(p, b, feats, k, apply=model.apply,
                                 cfg_key=cfg_key, dtype=dtype,
                                 half_batch=half_batch)
        if g1 is None:
            g1 = jax.device_get(g)
        p, m, v = adamw(p, g, m, v, t, cfg["learning_rate"],
                        cfg["weight_decay"])
        losses.append(float(loss))
    return {"losses": losses, "grad1": g1, "params3": jax.device_get(p)}


def _norms(tree) -> list:
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def leaf_gaps(cand: dict, ref: dict, params0) -> dict:
    """Per-leaf gaps of the first gradient's and the change's norms
    (None for a leaf the reference gradient does not move); see
    `compare`."""
    gc, gr = _norms(cand["grad1"]), _norms(ref["grad1"])
    med_g = float(np.median(gr))
    delta = lambda p: jax.tree.map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        p, params0)
    dc, dr = _norms(delta(cand["params3"])), _norms(delta(ref["params3"]))
    moving = [g >= STILL_LEAF * med_g for g in gr]
    med_d = float(np.median([d for d, m in zip(dr, moving) if m]))
    return {"grad": [abs(a - b) / max(b, med_g) for a, b in zip(gc, gr)],
            "update": [abs(a - b) / max(b, med_d) if m else None
                       for a, b, m in zip(dc, dr, moving)]}


def compare(cand: dict, ref: dict, params0) -> dict:
    """The numbers `correct` is decided on (see PERF.md):

    loss_gap    largest relative gap of the three step losses;
    grad_gap    worst leaf's gap between the norms of the first gradient,
                over the larger of that leaf's and the median leaf's
                reference norm;
    update_gap  the same for the parameters' change after three steps,
                over leaves the reference gradient moves (a leaf whose
                gradient is under `STILL_LEAF` of the median leaf's
                moves by round-off alone).
    """
    lc, lr = np.asarray(cand["losses"]), np.asarray(ref["losses"])
    g = leaf_gaps(cand, ref, params0)
    return {"loss_gap": float(np.max(np.abs(lc - lr) / np.abs(lr))),
            "grad_gap": float(max(g["grad"])),
            "update_gap": float(max(u for u in g["update"]
                                    if u is not None))}
