"""GNN correctness: sampled-tower forward equals a dense reference when the
fanout covers every neighbor (mode='all')."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GNNConfig
from repro.core import minibatch as mb
from repro.graphs.csr import DeviceGraph
from repro.models.gnn.models import apply_gnn, init_gnn


def _dense_sage_ref(graph, params, roots):
    """numpy full-neighborhood SAGE (mean aggregator, relu between)."""
    x = graph.features.astype(np.float64)
    L = len(params["layers"])
    h = x
    for li, p in enumerate(params["layers"]):
        nxt = np.zeros((graph.num_nodes, p["w_self"].shape[1]))
        for u in range(graph.num_nodes):
            nbr = graph.indices[graph.indptr[u]:graph.indptr[u + 1]]
            mean = h[nbr].mean(axis=0) if len(nbr) else h[u]
            nxt[u] = h[u] @ np.asarray(p["w_self"], np.float64) + \
                mean @ np.asarray(p["w_neigh"], np.float64) + \
                np.asarray(p["b"], np.float64)
        h = np.maximum(nxt, 0) if li < L - 1 else nxt
    return h[roots]


@pytest.fixture(scope="module")
def small_setup(tiny_graph):
    g = tiny_graph
    gdev = DeviceGraph.from_graph(g)
    cfg = GNNConfig("t", "sage", 2, 16, g.feat_dim, g.num_classes,
                    fanout=(64, 64), dropout=0.0)
    params = init_gnn(cfg, jax.random.key(0))
    return g, gdev, cfg, params


def test_sage_full_neighborhood_matches_dense(small_setup):
    g, gdev, cfg, params = small_setup
    max_deg = int(g.degrees().max())
    roots = g.train_ids[:32]
    caps = (g.num_nodes + 128, g.num_nodes + 128)
    batch = mb.build_batch(jax.random.key(0), gdev,
                           jnp.asarray(roots, jnp.int32),
                           jnp.asarray(g.labels),
                           (max_deg, max_deg), caps, 0.5, mode="all")
    feats = jnp.asarray(g.features)
    x = feats[jnp.minimum(batch.node_ids, g.num_nodes - 1)]
    logits = apply_gnn(cfg, params, batch, x, gdev.degrees)
    lv = np.asarray(batch.levels[0])
    lm = np.asarray(batch.label_mask)
    ref = _dense_sage_ref(g, params, lv[lm])
    got = np.asarray(logits)[lm]
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("model", ["sage", "gcn", "gat", "gat_res"])
def test_models_forward_finite(small_setup, model, tiny_graph):
    g, gdev, _, _ = small_setup
    cfg = GNNConfig("t", model, 3, 32, g.feat_dim, g.num_classes,
                    fanout=(5, 5, 5))
    params = init_gnn(cfg, jax.random.key(1))
    batch = mb.build_batch(jax.random.key(2), gdev,
                           jnp.asarray(g.train_ids[:64], jnp.int32),
                           jnp.asarray(g.labels), (5, 5, 5),
                           (512, 1024, 1536), 0.9)
    feats = jnp.asarray(g.features)
    x = feats[jnp.minimum(batch.node_ids, g.num_nodes - 1)]
    logits = apply_gnn(cfg, params, batch, x, gdev.degrees)
    assert logits.shape == (64, g.num_classes)
    assert bool(jnp.isfinite(logits).all())


def test_gnn_gradients_flow(small_setup):
    g, gdev, cfg, params = small_setup
    batch = mb.build_batch(jax.random.key(3), gdev,
                           jnp.asarray(g.train_ids[:32], jnp.int32),
                           jnp.asarray(g.labels), (4, 4), (512, 768), 1.0)
    feats = jnp.asarray(g.features)

    def loss(p):
        x = feats[jnp.minimum(batch.node_ids, g.num_nodes - 1)]
        lg = apply_gnn(cfg, p, batch, x, gdev.degrees)
        from repro.train.losses import gnn_softmax_ce
        return gnn_softmax_ce(lg, batch.labels,
                              batch.label_mask.astype(jnp.float32))

    grads = jax.grad(loss)(params)
    norms = [float(jnp.abs(x).sum()) for x in jax.tree.leaves(grads)]
    assert all(np.isfinite(norms)) and sum(norms) > 0


@pytest.mark.parametrize("model", ["sage", "gcn", "gat", "gat_res"])
def test_feats_global_matches_pregathered(small_setup, model, tiny_graph):
    """apply_gnn(..., feats_global=True) — layer 0 composing src_pos with
    batch.node_ids — must equal the legacy pre-gathered-x path."""
    g, gdev, _, _ = small_setup
    cfg = GNNConfig("t", model, 2, 32, g.feat_dim, g.num_classes,
                    fanout=(4, 4), dropout=0.0)
    params = init_gnn(cfg, jax.random.key(4))
    batch = mb.build_batch(jax.random.key(5), gdev,
                           jnp.asarray(g.train_ids[:32], jnp.int32),
                           jnp.asarray(g.labels), (4, 4), (256, 384), 0.9)
    feats = jnp.asarray(g.features)
    x = feats[jnp.minimum(batch.node_ids, g.num_nodes - 1)]
    legacy = apply_gnn(cfg, params, batch, x, gdev.degrees)
    glob = apply_gnn(cfg, params, batch, feats, gdev.degrees,
                     feats_global=True)
    np.testing.assert_allclose(np.asarray(glob), np.asarray(legacy),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("model", ["sage", "gat_res"])
def test_train_steps_loss_trajectory_matches_across_agg_impl(tiny_graph,
                                                            model):
    """20 optimizer steps through the real trainer: the fused Pallas path
    (interpret mode here) must reproduce the jnp path's loss trajectory."""
    from repro.batching import make_policy
    from repro.configs.base import TrainConfig
    from repro.train.gnn_loop import GNNTrainer

    g = tiny_graph
    tcfg = TrainConfig(batch_size=128, max_epochs=2)
    pol = make_policy("comm_rand", mix=0.125, p=1.0)
    traj = {}
    for impl in ("jnp", "pallas"):
        cfg = GNNConfig("t", model, 2, 32, g.feat_dim, g.num_classes,
                        fanout=(4, 4), agg_impl=impl)
        traj[impl] = GNNTrainer(g, cfg, tcfg, pol, seed=0).train_steps(20)
    np.testing.assert_allclose(traj["pallas"], traj["jnp"],
                               rtol=1e-5, atol=1e-5)
