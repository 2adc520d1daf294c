"""Plain GraphSAGE (mean aggregator), as in Hamilton et al. 2017 and the
DGL defaults the paper trains with: per layer
`h_dst = h_self @ W_self + mean_{sampled neighbors}(h_src) @ W_neigh + b`,
ReLU and dropout between layers, no normalization. The neighbor mean is
over the valid sampled slots (duplicates count as often as drawn);
padded destination rows are zeroed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import dropout


def dims(cfg: dict) -> list:
    return [cfg["in_dim"]] + [cfg["hidden_dim"]] * (cfg["num_layers"] - 1) \
        + [cfg["num_classes"]]


def init(cfg: dict, key):
    """Weights in the program's layout, made on the device from `key`."""
    d = dims(cfg)
    shapes = []
    for i in range(cfg["num_layers"]):
        shapes += [(d[i], d[i + 1]), (d[i], d[i + 1])]
    ws = jax.jit(lambda k: [
        jax.random.normal(kk, s) / jnp.sqrt(float(s[0]))
        for kk, s in zip(jax.random.split(k, len(shapes)), shapes)])(key)
    return {"layers": [
        {"w_self": ws[2 * i], "w_neigh": ws[2 * i + 1],
         "b": jnp.zeros((d[i + 1],), jnp.float32)}
        for i in range(cfg["num_layers"])]}


def apply(cfg: dict, params, batch, feats, dkey):
    L = cfg["num_layers"]
    N = feats.shape[0]
    x = feats[jnp.minimum(batch["levels"][L], N - 1)]
    for i in range(L):
        p = params["layers"][i]
        hop = batch["hops"][L - 1 - i]
        m = hop["edge_mask"].astype(x.dtype)
        w = m / jnp.maximum(m.sum(axis=1, keepdims=True), 1)
        nbr = sum(w[:, j:j + 1] * x[hop["src_pos"][:, j]]
                  for j in range(w.shape[1]))
        x = x[hop["self_pos"]] @ p["w_self"] + nbr @ p["w_neigh"] + p["b"]
        x = x * hop["dst_mask"][:, None].astype(x.dtype)
        if i < L - 1:
            x = dropout(jax.nn.relu(x), jax.random.fold_in(dkey, i),
                        cfg["dropout"])
    return x
