"""`gat_res` (GAT with linear skips and averaged output heads, as
pytorch_geometric trains it on ogbn-products) against a plain float32
reference, its published parameter count, and the GraphSAGE train step
left as it was."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GNNConfig, TrainConfig
from repro.configs.registry import get_config
from repro.core import minibatch as mb
from repro.graphs.csr import DeviceGraph
from repro.models.gnn.models import apply_gnn, init_gnn
from repro.optim import adamw
from repro.train import gnn_loop
from repro.train.losses import gnn_softmax_ce


def _ref_layer(p, x, blk, last: bool):
    """One GAT layer with a skip, edge by edge: each destination's sampled
    slots, then its self edge, in a plain loop over the slots."""
    H, dh = p["a_src"].shape
    src, self_pos = np.asarray(blk.src_pos), np.asarray(blk.self_pos)
    emask = np.asarray(blk.edge_mask)
    z = (x @ p["w"]).reshape(-1, H, dh)
    score = lambda i, j: jax.nn.leaky_relu(
        jnp.einsum("nhd,hd->nh", z[j], p["a_src"]) +
        jnp.einsum("nhd,hd->nh", z[i], p["a_dst"]), 0.2)
    r = src.shape[1]
    scores = [jnp.where(emask[:, j, None], score(self_pos, src[:, j]),
                        -1e30) for j in range(r)]
    scores.append(score(self_pos, self_pos))
    alpha = jax.nn.softmax(jnp.stack(scores, axis=1), axis=1)
    out = alpha[:, r, :, None] * z[self_pos]
    for j in range(r):
        out = out + alpha[:, j, :, None] * z[src[:, j]]
    out = out.mean(axis=1) if last else out.reshape(-1, H * dh)
    out = out + p["b"] + x[self_pos] @ p["w_skip"] + p["b_skip"]
    return out * np.asarray(blk.dst_mask)[:, None]


def ref_gat_res(params, batch, feats):
    """Plain `gat_res` logits over a batch tower (no dropout)."""
    N = feats.shape[0]
    ids = np.asarray(batch.node_ids)
    x = feats[np.minimum(ids, N - 1)] * (ids < N)[:, None]
    L = len(batch.blocks)
    for i, blk in enumerate(batch.blocks):
        x = _ref_layer(params["layers"][i], x, blk, i == L - 1)
        if i < L - 1:
            x = jax.nn.elu(x)
    return x


def _ref_ce(logits, labels, mask):
    m = mask.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return ((lse - picked) * m).sum() / jnp.maximum(m.sum(), 1.0)


@pytest.fixture(scope="module")
def gat_res_setup(tiny_graph):
    g = tiny_graph
    gdev = DeviceGraph.from_graph(g)
    batch = mb.build_batch(jax.random.key(11), gdev,
                           jnp.asarray(g.train_ids[:48], jnp.int32),
                           jnp.asarray(g.labels), (4, 3), (256, 512), 0.9)
    return g, gdev, batch


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_gat_res_matches_reference(gat_res_setup, impl):
    """Logits, loss and gradients of the program's `gat_res` (the trainer's
    `feats_global` path) against the plain reference, on seeded random
    weights with non-zero biases; the kernel path in interpret mode.
    Tolerances: float32 sums of at most a few hundred terms, against
    the reference at `highest` precision."""
    g, gdev, batch = gat_res_setup
    cfg = GNNConfig("t", "gat_res", 2, 24, g.feat_dim, g.num_classes,
                    fanout=(4, 3), gat_heads=3, dropout=0.0, agg_impl=impl)
    params = init_gnn(cfg, jax.random.key(3))
    # non-zero biases and sharper attention, so each term shows
    leaves, tree = jax.tree.flatten(params)
    ks = jax.random.split(jax.random.key(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        l + 0.3 * jax.random.normal(k, l.shape) for l, k in zip(leaves, ks)])
    feats = jnp.asarray(g.features)
    labels, mask = batch.labels, batch.label_mask

    def prog_loss(p):
        lg = apply_gnn(cfg, p, batch, feats, gdev.degrees,
                       feats_global=True)
        return gnn_softmax_ce(lg, labels, mask.astype(jnp.float32)), lg

    def ref_loss(p):
        lg = ref_gat_res(p, batch, feats)
        return _ref_ce(lg, labels, mask), lg

    (loss, logits), grads = jax.value_and_grad(prog_loss, has_aux=True)(
        params)
    with jax.default_matmul_precision("highest"):
        (rloss, rlogits), rgrads = jax.value_and_grad(
            ref_loss, has_aux=True)(params)
    lm = np.asarray(mask)
    assert lm.sum() == 48
    np.testing.assert_allclose(np.asarray(logits)[lm],
                               np.asarray(rlogits)[lm], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(float(loss), float(rloss), rtol=1e-5)
    for path, gp, gr in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(grads), jax.tree.leaves(rgrads)):
        scale = float(jnp.abs(gr).max())
        assert scale > 0, path[0]
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4 * scale,
                                   err_msg=str(path[0]))


def test_gat_res_published_parameter_count():
    """PyG's GAT on ogbn-products, 3 layers of 4 heads at hidden 128 per
    head with linear skips: 751,574 parameters (OGB leaderboard)."""
    cfg = get_config("gat-products")
    shapes = jax.eval_shape(lambda k: init_gnn(cfg, k), jax.random.key(0))
    sizes = [int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)]
    assert sum(sizes) == 751_574
    # per layer: GATConv (lin, att_src, att_dst, bias) + skip Lin
    per_layer = [sum(int(np.prod(v.shape)) for v in l.values())
                 for l in shapes["layers"]]
    assert per_layer == [52_736 + 51_712, 263_680 + 262_656,
                         96_679 + 24_111]


# sha256 of the GraphSAGE train step's jaxpr at the size below, taken at
# the commit before `gat_res` was added: the SAGE path is left exactly
# as it was
SAGE_STEP_JAXPR = {
    "jnp": "076fd9c1530bb2e3c8e010db577debe6233f913ed6efcfd8c16e6a88ec418800",
    "pallas":
        "486b22b053bb5c4a9853cb1910488725f0319f56b347b7807e8a382751f7aff9",
}


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_sage_train_step_jaxpr_unchanged(gat_res_setup, impl):
    g, gdev, batch = gat_res_setup
    cfg = GNNConfig("t", "sage", 2, 32, g.feat_dim, g.num_classes,
                    fanout=(4, 3), agg_impl=impl)
    step, _ = gnn_loop._make_steps(cfg, TrainConfig(batch_size=48))
    params = init_gnn(cfg, jax.random.key(0))
    jaxpr = jax.make_jaxpr(step)(
        params, adamw.init(params), batch, jnp.asarray(g.features),
        gdev.degrees, 1e-3, jax.random.key(1), None, 1.0, jnp.int32(0))
    digest = hashlib.sha256(str(jaxpr).encode()).hexdigest()
    assert digest == SAGE_STEP_JAXPR[impl]
