"""Plain GAT with linear skips and averaged output heads, as
pytorch_geometric's `examples/ogbn_products_gat.py` trains it on
ogbn-products (Velickovic et al. 2018; the OGB leaderboard's "GAT
(NeighborSampling)"), over sampled blocks, in the program's layout.

Per layer, with H heads of width dh (hidden layers: `hidden_dim // H`;
the last layer: the class count): `z = h @ W` split into heads; the
score of edge (dst i, src j) is `LeakyReLU_0.2(a_src . z_j + a_dst .
z_i)`, masked to the valid sampled slots; one self edge per destination
(score `LeakyReLU(a_src . z_i + a_dst . z_i)`) joins the softmax; no
attention dropout. The output is the alpha-weighted sum of the sources'
z plus alpha_self * z_i; the heads are concatenated in the hidden layers
and averaged in the last; then the bias, and the skip `h_i @ W_skip +
b_skip` of the destination's input row. ELU, then dropout, between
layers; padded destination rows are zeroed. Layer 0 reads the input
level's features with padded rows zeroed. A neighbor the sampler drew
twice counts twice, as the program's sampler draws with replacement
(PyG's draws without).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import dropout
from chipbench.reference.sage import dims


def _shapes(cfg: dict):
    d, H, L = dims(cfg), cfg["heads"], cfg["num_layers"]
    out = []
    for i in range(L):
        dh = d[i + 1] if i == L - 1 else d[i + 1] // H
        out.append({"w": (d[i], H * dh), "a": (H, dh), "b": (d[i + 1],),
                    "w_skip": (d[i], d[i + 1])})
    return out


def init(cfg: dict, key):
    """Weights in the program's layout, made on the device from `key`:
    `W` and `W_skip` normal over the root of their fan-in, the attention
    vectors normal over the root of the head width (unit-scale scores),
    biases zero."""
    shapes = _shapes(cfg)

    @jax.jit
    def make(key):
        layers = []
        for i, s in enumerate(shapes):
            ks = jax.random.split(jax.random.fold_in(key, i), 4)
            nrm = lambda k, sh, fan: jax.random.normal(k, sh) \
                / jnp.sqrt(float(fan))
            layers.append({
                "w": nrm(ks[0], s["w"], s["w"][0]),
                "a_src": nrm(ks[1], s["a"], s["a"][1]),
                "a_dst": nrm(ks[2], s["a"], s["a"][1]),
                "b": jnp.zeros(s["b"], jnp.float32),
                "w_skip": nrm(ks[3], s["w_skip"], s["w_skip"][0]),
                "b_skip": jnp.zeros(s["b"], jnp.float32)})
        return {"layers": layers}

    return make(key)


def _layer(p, x, hop, last: bool):
    H, dh = p["a_src"].shape
    src, self_pos, emask = hop["src_pos"], hop["self_pos"], hop["edge_mask"]
    z = (x @ p["w"]).reshape(-1, H, dh)
    s_src = jnp.einsum("nhd,hd->nh", z, p["a_src"])
    z_self = z[self_pos]
    s_dst = jnp.einsum("nhd,hd->nh", z_self, p["a_dst"])
    e = jax.nn.leaky_relu(s_src[src] + s_dst[:, None], 0.2)
    e = jnp.where(emask[..., None], e, jnp.asarray(-1e30, e.dtype))
    e_self = jax.nn.leaky_relu(s_src[self_pos] + s_dst, 0.2)
    alpha = jax.nn.softmax(jnp.concatenate([e, e_self[:, None]], axis=1),
                           axis=1)
    out = alpha[:, -1, :, None] * z_self
    for j in range(src.shape[1]):
        out = out + alpha[:, j, :, None] * z[src[:, j]]
    out = out.mean(axis=1) if last else out.reshape(out.shape[0], H * dh)
    return out + p["b"] + x[self_pos] @ p["w_skip"] + p["b_skip"]


def apply(cfg: dict, params, batch, feats, dkey):
    L = cfg["num_layers"]
    N = feats.shape[0]
    ids = batch["levels"][L]
    x = feats[jnp.minimum(ids, N - 1)] * (ids < N)[:, None].astype(
        feats.dtype)
    for i in range(L):
        hop = batch["hops"][L - 1 - i]
        x = _layer(params["layers"][i], x, hop, i == L - 1)
        x = x * hop["dst_mask"][:, None].astype(x.dtype)
        if i < L - 1:
            x = dropout(jax.nn.elu(x), jax.random.fold_in(dkey, i),
                        cfg["dropout"])
    return x
