"""GNN model zoo on static-shape mini-batch towers: GraphSAGE (paper's
primary), GCN and GAT (paper §6.4), and GAT as PyG trains it on
ogbn-products (`gat_res`).

All layers consume a `Block` (dense (n_dst, fanout) source-position gather +
self position), so aggregation is a per-edge-weighted reduce over the fanout
axis — exactly the shape of the fused `repro.kernels.gather_agg` Pallas
kernel. Every layer expresses its aggregation as scalar per-edge weights
(SAGE: mask/count, GCN: folded degree normalizers, GAT: attention alphas)
over one shared `gather_agg` call, so the (n_dst, fanout, F) gathered
intermediate never materializes in HBM on the kernel path — forward or
backward. `GNNConfig.agg_impl` selects the backend (see
`repro.kernels.gather_agg.ops.resolve_agg_impl`).

Model ids:

- `sage`, `gcn`: one weight per layer width; ReLU and dropout between
  layers.
- `gat`: `gat_heads` heads of `width // gat_heads`, concatenated; where
  the heads do not fill the width (the last layer), `w_out` projects them
  to it. ReLU between layers.
- `gat_res`: GAT (Velickovic et al. 2018) as pytorch_geometric's
  `examples/ogbn_products_gat.py` builds it (the OGB leaderboard's
  "GAT (NeighborSampling)"). Hidden layers: `gat_heads` heads of
  `hidden_dim // gat_heads`, concatenated, plus a bias; the last layer:
  `gat_heads` heads of `num_classes`, averaged, plus a bias. Every layer
  adds a linear skip `x_dst @ w_skip + b_skip` of its destinations' input
  rows. ELU, then dropout, between layers. Scores `LeakyReLU_0.2(a_src .
  z_j + a_dst . z_i)` over the sampled edges plus one self edge per
  destination, softmax over them, no attention dropout. Departures from
  PyG: the program's samplers draw `fanout` neighbors with replacement
  (PyG's `NeighborSampler` draws without), so a neighbor drawn twice
  counts twice in the softmax; PyG removes a sampled self edge before it
  adds the self loop, where the blocks here hold no such edge as the
  generated graphs have no self edges (a real graph's would be kept as a
  second self edge); padded destination rows are zeroed after every
  layer.

`apply_gnn(..., feats_global=True)` additionally composes layer-0 source
positions with `batch.node_ids`, gathering input features straight from the
global (N, F) feature matrix — the per-batch HBM feature traffic is then
exactly the paper's Fig-6 working-set metric, with no up-front (cap_L, F)
copy.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import GNNConfig
from repro.core.minibatch import MiniBatch
from repro.kernels.gather_agg.ops import gather_agg, resolve_agg_impl
from repro.kernels.gather_cached.ops import gather_cached
from repro.models.lm.common import dense_init

Params = Dict


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_gnn(cfg: GNNConfig, key) -> Params:
    dims = [cfg.in_dim] + [cfg.hidden_dim] * (cfg.num_layers - 1) \
        + [cfg.num_classes]
    layers = []
    for i in range(cfg.num_layers):
        k = jax.random.fold_in(key, i)
        ks = jax.random.split(k, 6)
        din, dout = dims[i], dims[i + 1]
        if cfg.model == "sage":
            layers.append({
                "w_self": dense_init(ks[0], (din, dout)),
                "w_neigh": dense_init(ks[1], (din, dout)),
                "b": jnp.zeros((dout,)),
            })
        elif cfg.model == "gcn":
            layers.append({
                "w": dense_init(ks[0], (din, dout)),
                "b": jnp.zeros((dout,)),
            })
        elif cfg.model == "gat":
            H = cfg.gat_heads
            dh = max(dout // H, 1)
            layers.append({
                "w": dense_init(ks[0], (din, H * dh)),
                "a_src": dense_init(ks[1], (H, dh)) * 0.1,
                "a_dst": dense_init(ks[2], (H, dh)) * 0.1,
                "b": jnp.zeros((H * dh,)),
                "w_out": dense_init(ks[3], (H * dh, dout))
                if H * dh != dout else None,
            })
        elif cfg.model == "gat_res":
            H = cfg.gat_heads
            last = i == cfg.num_layers - 1
            # hidden: heads of hidden_dim // H, concatenated; last: heads
            # of num_classes, averaged (a bias one head wide says so)
            dh = dout if last else dout // H
            layers.append({
                "w": dense_init(ks[0], (din, H * dh)),
                "a_src": dense_init(ks[1], (H, dh)) * 0.1,
                "a_dst": dense_init(ks[2], (H, dh)) * 0.1,
                "b": jnp.zeros((dout,)),
                "w_skip": dense_init(ks[4], (din, dout)),
                "b_skip": jnp.zeros((dout,)),
            })
        else:
            raise ValueError(cfg.model)
    return {"layers": layers}


# ---------------------------------------------------------------------------
# layers — each one reduces to gather_agg(x_tab, src_idx, per-edge weights).
# `x_tab` is the source feature table: the previous level's activations, or
# the GLOBAL feature matrix at layer 0 under feats_global (src_idx then
# holds composed global row ids).
# ---------------------------------------------------------------------------
def _masked_mean(x_tab, src_idx, edge_mask, impl: str = "jnp"):
    """(n_dst, r)-indexed mean over valid neighbor slots -> (n_dst, F)."""
    m = edge_mask.astype(jnp.float32)
    w = m / jnp.maximum(m.sum(axis=1, keepdims=True), 1.0)
    return gather_agg(x_tab, src_idx, w, impl=impl).astype(x_tab.dtype)


def sage_layer(p, x_tab, src_idx, self_idx, edge_mask, *, impl="jnp"):
    h_self = x_tab[self_idx]
    h_nbr = _masked_mean(x_tab, src_idx, edge_mask, impl)
    return h_self @ p["w_self"] + h_nbr @ p["w_neigh"] + p["b"]


def gcn_layer(p, x_tab, src_idx, self_idx, edge_mask, deg_src_edge, deg_dst,
              *, impl="jnp"):
    """Symmetric-normalized aggregation with self loops (global degrees).

    All normalizers fold into the per-edge weight: mask * rsqrt(deg_src+1)
    * (deg_dst / sampled_count)  * rsqrt(deg_dst+1) — deg_dst/count
    compensates fanout subsampling."""
    m = edge_mask.astype(jnp.float32)
    cnt = jnp.maximum(edge_mask.sum(axis=1, keepdims=True), 1)
    c_src = jax.lax.rsqrt(deg_src_edge.astype(jnp.float32) + 1.0)
    c_dst = jax.lax.rsqrt(deg_dst.astype(jnp.float32) + 1.0)
    w = m * c_src * (deg_dst[:, None] / cnt) * c_dst[:, None]
    agg = gather_agg(x_tab, src_idx, w, impl=impl).astype(x_tab.dtype)
    h_self = x_tab[self_idx] * (c_dst * c_dst)[:, None].astype(x_tab.dtype)
    return (agg + h_self) @ p["w"] + p["b"]


def _merge_heads(out, b):
    """(n_dst, H, dh) head outputs -> concatenated (n_dst, H * dh), or
    averaged (n_dst, dh) where the bias is one head wide (`gat_res`'s last
    layer)."""
    n_dst, H, dh = out.shape
    if H > 1 and b.shape[-1] == dh:
        return out.mean(axis=1)
    return out.reshape(n_dst, H * dh)


def gat_layer(p, x_tab, src_idx, self_idx, edge_mask, *, impl="jnp",
              layer: int = 0):
    """One GAT layer; its phases run under the named scopes
    `gat/l<layer>/project`, `.../scores` (source and destination scores,
    LeakyReLU, mask), `.../softmax` and, where `p` has a skip weight,
    `.../skip`; the aggregation under the kernels' own scopes."""
    H, dh = p["a_src"].shape
    n_dst, r = src_idx.shape
    scope = f"gat/l{layer}"
    with jax.named_scope(f"{scope}/project"):
        z = (x_tab @ p["w"]).reshape(-1, H, dh)       # (n_src, H, dh)
    with jax.named_scope(f"{scope}/scores"):
        # per-SOURCE attention logits: scores are linear in z, so gather
        # the (n_src, H) scalars instead of (n_dst, r, H, dh) projected rows
        s_src = jnp.einsum("nhd,hd->nh", z, p["a_src"])
        z_self = z[self_idx]                          # (n_dst, H, dh)
        e_src = s_src[src_idx]                        # (n_dst, r, H)
        e_dst = jnp.einsum("nhd,hd->nh", z_self, p["a_dst"])
        e_self = jnp.einsum("nhd,hd->nh", z_self, p["a_src"]) + e_dst
        e = jax.nn.leaky_relu(e_src + e_dst[:, None], 0.2)  # (n_dst, r, H)
        e = jnp.where(edge_mask[..., None], e, -1e30)
    with jax.named_scope(f"{scope}/softmax"):
        e_all = jnp.concatenate(
            [e, jax.nn.leaky_relu(e_self, 0.2)[:, None]], axis=1)  # + self
        alpha = jax.nn.softmax(e_all, axis=1)         # (n_dst, r+1, H)
        a_nbr, a_self = alpha[:, :r], alpha[:, r]
    if impl == "pallas":
        # fold heads into the row axis: row (s*H + h) of zf is head h of
        # source s, so one gather_agg call reduces all heads, with alpha
        # flowing through the kernel's dw path for attention gradients
        zf = z.reshape(-1, dh)
        idx2 = (src_idx[:, None, :] * H +
                jnp.arange(H, dtype=src_idx.dtype)[None, :, None])
        w2 = a_nbr.transpose(0, 2, 1)                 # (n_dst, H, r)
        out = gather_agg(zf, idx2.reshape(n_dst * H, r),
                         w2.reshape(n_dst * H, r), impl=impl)
        out = out.reshape(n_dst, H, dh)
    else:
        out = jnp.einsum("nrh,nrhd->nhd", a_nbr, z[src_idx])
    out = out + a_self[..., None] * z_self
    out = _merge_heads(out, p["b"]) + p["b"]
    if p.get("w_out") is not None:
        out = out @ p["w_out"]
    if "w_skip" in p:
        with jax.named_scope(f"{scope}/skip"):
            out = out + x_tab[self_idx] @ p["w_skip"] + p["b_skip"]
    return out


# ---------------------------------------------------------------------------
# full model over a batch tower
# ---------------------------------------------------------------------------
def apply_gnn(cfg: GNNConfig, params: Params, batch: MiniBatch, x,
              degrees=None, *, train: bool = False, dropout_key=None,
              feats_global: bool = False, cache=None):
    """Returns logits aligned with batch.roots order.

    x: the input features. With feats_global=False (legacy), x is the
    pre-gathered (cap_L, in_dim) input-level table (callers do
    `feats[batch.node_ids]` — e.g. the sharded halo-gather path). With
    feats_global=True, x is the FULL (N, in_dim) feature matrix and layer 0
    gathers rows directly through composed `node_ids[src_pos]` indices — no
    (cap_L, in_dim) copy is ever made, so per-batch feature HBM reads equal
    the Fig-6 working-set bytes.

    cache: an optional `repro.featcache.CachePlan` or dynamic CLOCK
    `DynamicCacheState` — anything with `.cache` (C, F) rows and `.pos`
    (N,) map (requires feats_global=True). Layer-0 feature reads then
    route through the two-level `gather_cached` kernel: the
    (cap_L, in_dim) input level is assembled once per batch, each row
    served from the device-resident cache on hit and from the global
    matrix on miss. Cache rows are exact copies, so outputs are
    bit-identical to the uncached path regardless of residency; the
    trainer measures hit rates (and feeds dynamic admission) separately
    on the same position map. The gather backend follows `cfg.agg_impl`.
    """
    impl = resolve_agg_impl(cfg.agg_impl)
    L = len(batch.blocks)
    if cache is not None:
        if not feats_global:
            raise ValueError("cache= requires feats_global=True "
                             "(x must be the full (N, F) feature matrix)")
        x, _, _ = gather_cached(cache.cache, x, cache.pos, batch.node_ids,
                                impl=cfg.agg_impl)
        x = x * batch.node_mask[:, None].astype(x.dtype)
        feats_global = False
    elif not feats_global:
        x = x * batch.node_mask[:, None].astype(x.dtype)
    elif cfg.model in ("gat", "gat_res"):
        # GAT projects every unique source row BEFORE gathering (projecting
        # per edge would multiply the matmul FLOPs by the fanout), so the
        # input level is materialized once here; the per-edge (r, H*dh)
        # intermediates are still never built on the kernel path.
        x = x[jnp.minimum(batch.node_ids, x.shape[0] - 1)] \
            * batch.node_mask[:, None].astype(x.dtype)
        feats_global = False
    for i, block in enumerate(batch.blocks):
        p = params["layers"][i]
        if i == 0 and feats_global:
            gid = jnp.minimum(batch.node_ids, x.shape[0] - 1)
            src_idx = gid[block.src_pos]
            self_idx = gid[block.self_pos]
        else:
            src_idx, self_idx = block.src_pos, block.self_pos
        if cfg.model == "sage":
            x = sage_layer(p, x, src_idx, self_idx, block.edge_mask,
                           impl=impl)
        elif cfg.model == "gcn":
            # per-level degrees gathered from the global degree array;
            # blocks[i] maps level (L-i) -> (L-i-1)
            n = degrees.shape[0]
            d_src = degrees[jnp.minimum(batch.levels[L - i], n - 1)]
            deg_dst = degrees[jnp.minimum(batch.levels[L - i - 1], n - 1)]
            x = gcn_layer(p, x, src_idx, self_idx, block.edge_mask,
                          d_src[block.src_pos], deg_dst, impl=impl)
        else:
            x = gat_layer(p, x, src_idx, self_idx, block.edge_mask,
                          impl=impl, layer=i)
        x = x * block.dst_mask[:, None].astype(x.dtype)
        if i < len(batch.blocks) - 1:
            x = jax.nn.elu(x) if cfg.model == "gat_res" else jax.nn.relu(x)
            if train and cfg.dropout > 0 and dropout_key is not None:
                keep = 1.0 - cfg.dropout
                mask = jax.random.bernoulli(
                    jax.random.fold_in(dropout_key, i), keep, x.shape)
                x = jnp.where(mask, x / keep, 0.0)
    return x
