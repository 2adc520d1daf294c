"""Plain GAT (Velickovic et al. 2018) over sampled blocks, in the
program's stated layout.

Per layer, with H heads of width dh = out // H: `z = h @ W` split into
heads; the score of edge (dst i, src j) is
`LeakyReLU_0.2(a_dst . z_i + a_src . z_j)`, masked to the valid sampled
slots; a self edge (score `LeakyReLU(a_dst . z_i + a_src . z_i)`) joins
the softmax; the output is the alpha-weighted sum of the sources' z plus
alpha_self * z_i, heads concatenated, plus a bias. Departure from the
paper, as the program states it: where H * dh differs from the layer's
width (the last layer, 47 classes over 4 heads), the concatenated heads
are projected by `W_out` instead of averaged. Layer 0 reads the input
level's features with padded rows zeroed. ReLU and dropout between
layers; padded destination rows are zeroed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import dropout
from chipbench.reference.sage import dims


def _shapes(cfg: dict):
    d, H = dims(cfg), cfg["heads"]
    out = []
    for i in range(cfg["num_layers"]):
        dh = max(d[i + 1] // H, 1)
        out.append({"w": (d[i], H * dh), "a_src": (H, dh), "a_dst": (H, dh),
                    "w_out": (H * dh, d[i + 1])
                    if H * dh != d[i + 1] else None})
    return out


def init(cfg: dict, key):
    """Weights in the program's layout, made on the device from `key`."""
    shapes = _shapes(cfg)

    @jax.jit
    def make(key):
        layers = []
        for i, s in enumerate(shapes):
            ks = jax.random.split(jax.random.fold_in(key, i), 4)
            nrm = lambda k, sh, scale=1.0: scale * jax.random.normal(k, sh) \
                / jnp.sqrt(float(sh[0]))
            layers.append({
                "w": nrm(ks[0], s["w"]),
                "a_src": nrm(ks[1], s["a_src"], 0.1),
                "a_dst": nrm(ks[2], s["a_dst"], 0.1),
                "b": jnp.zeros((s["w"][1],), jnp.float32),
                "w_out": None if s["w_out"] is None else nrm(ks[3],
                                                             s["w_out"])})
        return {"layers": layers}

    return make(key)


def _layer(p, x, hop):
    H, dh = p["a_src"].shape
    src, self_pos, emask = hop["src_pos"], hop["self_pos"], hop["edge_mask"]
    z = (x @ p["w"]).reshape(-1, H, dh)
    s_src = jnp.einsum("nhd,hd->nh", z, p["a_src"])
    z_self = z[self_pos]
    s_dst = jnp.einsum("nhd,hd->nh", z_self, p["a_dst"])
    e = jax.nn.leaky_relu(s_src[src] + s_dst[:, None], 0.2)
    e = jnp.where(emask[..., None], e, jnp.asarray(-1e30, e.dtype))
    e_self = jax.nn.leaky_relu(
        jnp.einsum("nhd,hd->nh", z_self, p["a_src"]) + s_dst, 0.2)
    alpha = jax.nn.softmax(jnp.concatenate([e, e_self[:, None]], axis=1),
                           axis=1)
    out = alpha[:, -1, :, None] * z_self
    for j in range(src.shape[1]):
        out = out + alpha[:, j, :, None] * z[src[:, j]]
    out = out.reshape(out.shape[0], H * dh) + p["b"]
    if p.get("w_out") is not None:
        out = out @ p["w_out"]
    return out


def apply(cfg: dict, params, batch, feats, dkey):
    L = cfg["num_layers"]
    N = feats.shape[0]
    ids = batch["levels"][L]
    x = feats[jnp.minimum(ids, N - 1)] * (ids < N)[:, None].astype(
        feats.dtype)
    for i in range(L):
        hop = batch["hops"][L - 1 - i]
        x = _layer(params["layers"][i], x, hop)
        x = x * hop["dst_mask"][:, None].astype(x.dtype)
        if i < L - 1:
            x = dropout(jax.nn.relu(x), jax.random.fold_in(dkey, i),
                        cfg["dropout"])
    return x
