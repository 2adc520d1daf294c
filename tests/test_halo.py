"""Halo-gather correctness on REAL GNN artifacts.

In-process tests drive `halo_gather_np` — the host mirror one subprocess
test in tests/test_dist_gnn.py pins `==` the shard_map device path — on
the pinned `tiny` graph: a real community shard plan, real sampled batch
node ids from the real `BatchStream`, features reconstructed exactly at
the dropless budget, out-of-budget requests dropped-and-counted (never
wrong), and the h == D/2 ring-dedup regression. The subprocess test runs
the same real-artifact gather through `dist.gnn.gather_batch_features`
under `shard_map` on 4 fake devices (the conftest pins the main process
to ONE device) for both halo and global modes.
"""
import os
import subprocess
import sys

import numpy as np

from repro.batching.stream import BatchStream
from repro.core import halo
from repro.dist import gnn as dist_gnn


def _shard_feats(plan, graph):
    """(D, Ns, F) shard-local layout of the real feature matrix."""
    local = np.zeros((plan.n_padded, graph.feat_dim), np.float32)
    valid = plan.perm >= 0
    local[valid] = np.asarray(graph.features)[plan.perm[valid]]
    return local.reshape(plan.n_shards, plan.n_per_shard, graph.feat_dim)


def _batch_rids(plan, stream, epoch=0, pos=0):
    """Real sampled batch node ids for every replica, remapped to the
    padded slot space (sentinel -> n_padded). Returns (ids, rids)."""
    d = plan.n_shards
    rb = stream.root_batches(epoch)[pos]
    bs = len(rb) // d
    ids = []
    for r in range(d):
        b = stream.build(rb[r * bs:(r + 1) * bs], epoch, pos)
        ids.append(np.asarray(b.node_ids))
    ids = np.stack(ids)                                  # (D, K) global
    n = plan.n_nodes
    rids = np.where(ids < n, plan.shard_pos[np.minimum(ids, n - 1)],
                    plan.n_padded)
    return ids, rids


def test_real_batch_roundtrip_dropless(tiny_graph):
    """Real comm_rand batch ids through the halo exchange at the
    trainer's budget (r_cap = cap_L, halo = ring max): every valid row
    is the exact global feature row, sentinels are zero rows, nothing
    is dropped."""
    plan = dist_gnn.community_shard_plan(tiny_graph, 4)
    stream = BatchStream(tiny_graph, "comm_rand", 32, (5, 5), (512, 1024),
                         seed=3)
    ids, rids = _batch_rids(plan, stream)
    feats = _shard_feats(plan, tiny_graph)
    out, dropped = halo.halo_gather_np(
        feats, rids, n_per_shard=plan.n_per_shard, r_cap=ids.shape[1],
        halo=2)
    assert int(dropped.sum()) == 0
    n = plan.n_nodes
    want = np.where((ids < n)[..., None],
                    np.asarray(tiny_graph.features)[np.minimum(ids, n - 1)],
                    0.0)
    np.testing.assert_array_equal(out, want)


def test_out_of_budget_rows_drop_never_corrupt(tiny_graph):
    """Starved budget (tiny r_cap): dropped requests are COUNTED and
    their rows stay exactly zero — a served row is still exact. The
    budget failure mode is visible, never silent corruption."""
    plan = dist_gnn.community_shard_plan(tiny_graph, 4)
    stream = BatchStream(tiny_graph, "comm_rand", 32, (5, 5), (512, 1024),
                         seed=3)
    ids, rids = _batch_rids(plan, stream)
    feats = _shard_feats(plan, tiny_graph)
    out, dropped = halo.halo_gather_np(
        feats, rids, n_per_shard=plan.n_per_shard, r_cap=2, halo=1)
    assert int(dropped.sum()) > 0            # the starvation actually bites
    n = plan.n_nodes
    want = np.where((ids < n)[..., None],
                    np.asarray(tiny_graph.features)[np.minimum(ids, n - 1)],
                    0.0)
    d, k = ids.shape
    for r in range(d):
        for j in range(k):
            row = out[r, j]
            assert np.array_equal(row, want[r, j]) or \
                not row.any(), (r, j)


def test_half_ring_dedup_regression():
    """Pinned regression: at h == D/2 the +h and -h directions reach the
    SAME shard; visiting it twice doubled every row it served. Both the
    D=4/halo=2 and D=2/halo=1 rings must reconstruct exactly once."""
    rng = np.random.default_rng(7)
    for d in (2, 4):
        ns, f = 6, 3
        feats = rng.normal(size=(d, ns, f)).astype(np.float32)
        flat = feats.reshape(d * ns, f)
        # every request targets the diametrically opposite shard
        ids = np.stack([
            rng.integers(((r + d // 2) % d) * ns,
                         ((r + d // 2) % d + 1) * ns, 5)
            for r in range(d)])
        out, dropped = halo.halo_gather_np(
            feats, ids, n_per_shard=ns, r_cap=5, halo=d // 2)
        assert int(dropped.sum()) == 0
        np.testing.assert_array_equal(out, flat[ids])   # not 2 * flat[ids]


def test_collective_bytes_model_orders():
    """The napkin model the halo planner compares against: ring bytes
    grow with halo distance and are independent of D; the global
    fallback grows with D."""
    k, f = 1024, 64
    ring1 = halo.collective_bytes_model(k, f, 8, k, 1, "halo")
    ring2 = halo.collective_bytes_model(k, f, 8, k, 2, "halo")
    assert ring2 == 2 * ring1
    assert ring1 == halo.collective_bytes_model(k, f, 64, k, 1, "halo")
    g8 = halo.collective_bytes_model(k, f, 8, 0, 0, "global")
    g64 = halo.collective_bytes_model(k, f, 64, 0, 0, "global")
    assert g64 > g8


GNN_HALO_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_platform_name", "cpu")
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.batching.stream import BatchStream
from repro.core.reorder import prepare
from repro.dist import gnn as dist_gnn
from repro.graphs import synthetic

g = prepare(synthetic.load("tiny"), oracle=True)
plan = dist_gnn.community_shard_plan(g, 4)
mesh = dist_gnn.make_gnn_mesh(4)
stream = BatchStream(g, "comm_rand", 32, (5, 5), (512, 1024), seed=3)
rb = stream.root_batches(0)[0]
ids = np.stack([np.asarray(stream.build(rb[r * 8:(r + 1) * 8], 0, 0)
                           .node_ids) for r in range(4)])
feats_local = plan.shard_features(g.features, mesh)
pos = plan.device_pos(mesh)
ids_sh = jax.device_put(jnp.asarray(ids), NamedSharding(mesh, P("shard")))
want = np.where((ids < g.num_nodes)[..., None],
                np.asarray(g.features)[np.minimum(ids, g.num_nodes - 1)],
                0.0)

for hplan in (dist_gnn.HaloPlan("halo", 2, ids.shape[1]),
              dist_gnn.HaloPlan("global", 0, 0)):
    def f(fl, p, il):
        rows, dropped = dist_gnn.gather_batch_features(
            fl, p, il[0], plan, hplan)
        return rows[None], dropped[None]
    fn = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("shard", None), P(), P("shard")),
        out_specs=(P("shard"), P("shard")), check_vma=False))
    out, dropped = fn(feats_local, pos, ids_sh)
    assert int(np.asarray(dropped).sum()) == 0, hplan
    np.testing.assert_array_equal(np.asarray(out), want)
print("GNN_HALO_OK")

# starved ring budget: drops are counted, rows never corrupted
hplan = dist_gnn.HaloPlan("halo", 1, 2)
def f2(fl, p, il):
    rows, dropped = dist_gnn.gather_batch_features(
        fl, p, il[0], plan, hplan)
    return rows[None], dropped[None]
out, dropped = jax.jit(jax.shard_map(
    f2, mesh=mesh, in_specs=(P("shard", None), P(), P("shard")),
    out_specs=(P("shard"), P("shard")), check_vma=False))(
        feats_local, pos, ids_sh)
assert int(np.asarray(dropped).sum()) > 0
out = np.asarray(out)
for r in range(4):
    for j in range(ids.shape[1]):
        assert np.array_equal(out[r, j], want[r, j]) or not out[r, j].any()
print("GNN_HALO_DROP_OK")
"""


def test_gnn_halo_gather_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", GNN_HALO_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "GNN_HALO_OK" in out.stdout and "GNN_HALO_DROP_OK" in out.stdout, \
        (out.stdout, out.stderr[-3000:])
