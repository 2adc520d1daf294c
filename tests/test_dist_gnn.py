"""repro.dist.gnn: community-sharded data-parallel GNN training.

The determinism headline (in-process, 1-replica mesh over the default
CPU device): sharded training is BIT-identical to single-device — exact
`==` on the 20-step loss trajectory and sha1-equal params. The 4-replica
behavior (convergence, per-replica streams concatenating to the exact
single-device epoch order, halo mirror == shard_map device path, Pallas
kernels under shard_map) runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4, per the conftest
contract that the main process sees ONE device.

Property tests (hypothesis; the `_hypothesis_stub` when the real package
is absent) pin the partition algebra on random community graphs: the
shard-position map is a bijection onto distinct padded slots, and the
host halo mirror reconstructs every cross-shard feature row exactly at
the dropless budget (r_cap = K, halo = D // 2).
"""
import hashlib
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import GNNConfig, TrainConfig
from repro.core import halo
from repro.dist import gnn as dist_gnn
from repro.train.gnn_loop import GNNTrainer


def _digest(tree) -> str:
    h = hashlib.sha1()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _cfg(graph, dropout=0.5):
    return GNNConfig(name="t", model="sage", num_layers=2, hidden_dim=16,
                     in_dim=graph.feat_dim, num_classes=graph.num_classes,
                     fanout=(5, 5), dropout=dropout)


def _tcfg(**kw):
    kw.setdefault("batch_size", 32)
    kw.setdefault("max_epochs", 2)
    return TrainConfig(seed=0, **kw)


# ---------------------------------------------------------------------------
# 1-replica mesh == single device, bit for bit (in-process: the default
# CPU device IS a valid 1-device mesh)
# ---------------------------------------------------------------------------
def test_one_replica_bit_identical_to_single_device(tiny_graph):
    cfg, tcfg = _cfg(tiny_graph), _tcfg()
    single = GNNTrainer(tiny_graph, cfg, tcfg, "comm_rand", seed=3)
    losses_s = single.train_steps(20)

    mesh = dist_gnn.make_gnn_mesh(1)
    sharded = GNNTrainer(tiny_graph, cfg, tcfg, "comm_rand", seed=3,
                         mesh=mesh)
    losses_m = sharded.train_steps(20)

    assert losses_s == losses_m          # exact ==, not allclose
    assert _digest(single.params) == _digest(sharded.params)
    assert _digest(single.opt_state) == _digest(sharded.opt_state)


def test_one_replica_plan_is_identity(tiny_graph):
    plan = dist_gnn.community_shard_plan(tiny_graph, 1)
    n = tiny_graph.num_nodes
    assert plan.n_per_shard == n and plan.n_padded == n
    np.testing.assert_array_equal(plan.shard_pos, np.arange(n))
    np.testing.assert_array_equal(plan.perm, np.arange(n))
    hp = dist_gnn.plan_halo(plan, tiny_graph, (5, 5), 128)
    assert hp.mode == "halo" and hp.halo == 0


def test_sharded_checkpoint_resume_bit_exact(tiny_graph, tmp_path):
    cfg, tcfg = _cfg(tiny_graph), _tcfg()
    mesh = dist_gnn.make_gnn_mesh(1)
    a = GNNTrainer(tiny_graph, cfg, tcfg, "comm_rand", seed=3, mesh=mesh,
                   ckpt_dir=str(tmp_path / "ck"), ckpt_every=5)
    losses_a = a.train_steps(10)
    # resume from the step-10 checkpoint: the continuation must replay
    # the uninterrupted run exactly (cursor + replicated-on-mesh state)
    b = GNNTrainer(tiny_graph, cfg, tcfg, "comm_rand", seed=3, mesh=mesh,
                   ckpt_dir=str(tmp_path / "ck"))
    assert b.global_step == 10
    c = GNNTrainer(tiny_graph, cfg, tcfg, "comm_rand", seed=3, mesh=mesh)
    losses_c = c.train_steps(10)
    assert losses_c == losses_a
    assert c.train_steps(5) == b.train_steps(5)
    assert _digest(b.params) == _digest(c.params)


def test_mesh_rejects_unsupported_modes(tiny_graph):
    mesh = dist_gnn.make_gnn_mesh(1)
    with pytest.raises(ValueError, match="pipeline"):
        GNNTrainer(tiny_graph, _cfg(tiny_graph), _tcfg(), "comm_rand",
                   mesh=mesh, pipeline="async")
    with pytest.raises(ValueError, match="dynamic"):
        GNNTrainer(tiny_graph, _cfg(tiny_graph), _tcfg(), "comm_rand",
                   mesh=mesh, cache="dynamic:degree_hot")
    # batch divisibility is checked against the mesh size; with a
    # 1-replica mesh any size divides, so assert via the stream directly
    plan2 = dist_gnn.ShardPlan(2, 4, 2, np.arange(4, dtype=np.int32),
                               np.arange(4, dtype=np.int64),
                               np.zeros(1, np.int32))
    with pytest.raises(ValueError, match="divisible"):
        dist_gnn.ShardedBatchStream(
            tiny_graph, "comm_rand", 33, (5, 5), (64, 128),
            mesh=mesh, plan=plan2)


# ---------------------------------------------------------------------------
# partition + halo-plan algebra (host-side, no mesh required)
# ---------------------------------------------------------------------------
def _random_community_graph(rng, n, n_comm, feat_dim=4):
    """A tiny CSR graph with contiguous community blocks (what
    `core.reorder.prepare` guarantees) and random intra/inter edges."""
    from repro.graphs.csr import Graph
    bounds = np.sort(rng.choice(np.arange(1, n), n_comm - 1,
                                replace=False)) if n_comm > 1 else []
    comm = np.zeros(n, np.int32)
    for b in bounds:
        comm[b:] += 1
    adj = [set() for _ in range(n)]
    for _ in range(n * 3):
        u, v = int(rng.integers(n)), int(rng.integers(n))
        if u != v:
            adj[u].add(v)
            adj[v].add(u)
    indptr = np.zeros(n + 1, np.int64)
    indices = []
    for u in range(n):
        nbrs = sorted(adj[u])
        indices.extend(nbrs)
        indptr[u + 1] = len(indices)
    ids = np.arange(n)
    return Graph(indptr=indptr, indices=np.asarray(indices, np.int32),
                 features=rng.normal(size=(n, feat_dim)).astype(np.float32),
                 labels=rng.integers(0, 3, n).astype(np.int32),
                 train_ids=ids, val_ids=ids[:2], test_ids=ids[:2],
                 communities=comm.astype(np.int32), name="prop")


@settings(max_examples=10)
@given(n=st.integers(8, 60), n_comm=st.integers(1, 6),
       n_shards=st.integers(1, 5), seed=st.integers(0, 10 ** 6))
def test_shard_pos_is_a_bijection(n, n_comm, n_shards, seed):
    rng = np.random.default_rng(seed)
    g = _random_community_graph(rng, n, min(n_comm, n))
    plan = dist_gnn.community_shard_plan(g, n_shards)
    # injective onto distinct padded slots...
    assert len(np.unique(plan.shard_pos)) == n
    assert plan.shard_pos.min() >= 0
    assert plan.shard_pos.max() < plan.n_padded
    # ...and perm inverts it exactly; every non-slot is the -1 sentinel
    np.testing.assert_array_equal(plan.perm[plan.shard_pos], np.arange(n))
    assert (plan.perm >= 0).sum() == n
    # communities are never split across shards
    owner = plan.shard_of_node
    comm = np.asarray(g.communities)
    for c in np.unique(comm):
        assert len(np.unique(owner[comm == c])) == 1


@settings(max_examples=10)
@given(n=st.integers(8, 48), n_comm=st.integers(1, 5),
       n_shards=st.integers(2, 4), k=st.integers(3, 16),
       seed=st.integers(0, 10 ** 6))
def test_halo_roundtrip_reconstructs_cross_shard_rows(n, n_comm, n_shards,
                                                      k, seed):
    """community partition -> halo exchange -> every requested feature
    row (cross-shard included) is reconstructed EXACTLY at the dropless
    budget; sentinel ids come back as zero rows."""
    rng = np.random.default_rng(seed)
    g = _random_community_graph(rng, n, min(n_comm, n))
    plan = dist_gnn.community_shard_plan(g, n_shards)
    d, ns = plan.n_shards, plan.n_per_shard
    local = np.zeros((plan.n_padded, g.feat_dim), np.float32)
    valid = plan.perm >= 0
    local[valid] = g.features[plan.perm[valid]]

    ids = rng.integers(0, n + 3, size=(d, k))          # n.. are sentinels
    rid = np.where(ids < n, plan.shard_pos[np.minimum(ids, n - 1)],
                   plan.n_padded)
    out, dropped = halo.halo_gather_np(
        local.reshape(d, ns, g.feat_dim), rid,
        n_per_shard=ns, r_cap=k, halo=d // 2)
    assert int(dropped.sum()) == 0
    want = np.where((ids < n)[..., None],
                    g.features[np.minimum(ids, n - 1)], 0.0)
    np.testing.assert_array_equal(out, want)


@settings(max_examples=6)
@given(n_shards=st.integers(2, 5), seed=st.integers(0, 10 ** 6))
def test_plan_halo_budget_covers_reachability(n_shards, seed):
    rng = np.random.default_rng(seed)
    g = _random_community_graph(rng, 40, 5)
    plan = dist_gnn.community_shard_plan(g, n_shards)
    hp = dist_gnn.plan_halo(plan, g, (5, 5), 64, mode="halo")
    assert hp.mode == "halo"
    assert 0 <= hp.halo <= n_shards // 2      # ring distance cap
    assert hp.r_cap == 64
    # restricting roots to one replica's communities can only shrink it
    rb = np.tile(np.arange(n_shards * 4) % g.num_nodes,
                 (2, 1)).astype(np.int64)
    hp_rooted = dist_gnn.plan_halo(plan, g, (5, 5), 64,
                                   root_batches=rb, mode="halo")
    assert hp_rooted.halo <= hp.halo


def test_plan_halo_auto_falls_back_to_global(tiny_graph):
    """mode="auto" degrades to the all-gather fallback exactly when the
    forced ring plan's modeled bytes exceed the global gather's."""
    plan = dist_gnn.community_shard_plan(tiny_graph, 4)
    forced = dist_gnn.plan_halo(plan, tiny_graph, (5, 5), 1024,
                                mode="halo")
    auto = dist_gnn.plan_halo(plan, tiny_graph, (5, 5), 1024)
    hb = forced.bytes_per_gather(1024, tiny_graph.feat_dim, 4)
    gb = dist_gnn.HaloPlan("global", 0, 0).bytes_per_gather(
        1024, tiny_graph.feat_dim, 4)
    if hb > gb:
        assert auto == dist_gnn.HaloPlan("global", 0, 0)
    else:                                   # cheap ring: halo stands
        assert auto == forced
    # explicit mode="global" always wins
    forced_g = dist_gnn.plan_halo(plan, tiny_graph, (5, 5), 1024,
                                  mode="global")
    assert forced_g.mode == "global"


# ---------------------------------------------------------------------------
# 4-replica mesh (subprocess: conftest pins the main process to 1 device)
# ---------------------------------------------------------------------------
FOUR_REPLICA_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np, jax.numpy as jnp
jax.config.update("jax_platform_name", "cpu")
assert jax.device_count() == 4
from jax.sharding import PartitionSpec as P
from repro.core import halo
from repro.core.reorder import prepare
from repro.graphs import synthetic
from repro.configs.base import GNNConfig, TrainConfig
from repro.train.gnn_loop import GNNTrainer
from repro.dist import gnn as dist_gnn

g = prepare(synthetic.load("tiny"), oracle=True)
cfg = GNNConfig(name="t", model="sage", num_layers=2, hidden_dim=16,
                in_dim=g.feat_dim, num_classes=g.num_classes,
                fanout=(5, 5), dropout=0.5)
tcfg = TrainConfig(batch_size=32, max_epochs=3, seed=0)
mesh = dist_gnn.make_gnn_mesh(4)
tr = GNNTrainer(g, cfg, tcfg, "comm_rand", seed=3, mesh=mesh)

# per-replica root slices concatenate to the EXACT single-device order
single = GNNTrainer(g, cfg, tcfg, "comm_rand", seed=3)
for epoch in (0, 1):
    rb = tr.stream.replica_root_batches(epoch)
    assert rb.shape[1] == 4
    np.testing.assert_array_equal(
        rb.reshape(rb.shape[0], -1), single.stream.root_batches(epoch))
print("CONCAT_OK")

losses = tr.train_steps(40)
assert np.isfinite(losses).all()
assert losses[-1] < losses[0], (losses[0], losses[-1])
ev = tr.evaluate(g.val_ids)
assert np.isfinite(ev["loss"]) and 0.0 <= ev["acc"] <= 1.0
# eval runs on one device: on a TPU the compiler cannot partition the
# Pallas kernels of a jit over mesh-placed inputs
assert all(len(x.devices()) == 1
           for x in jax.tree.leaves(tr._eval_state()))
print("CONVERGE_OK")

# forced halo-mode plan trains too (dropless: r_cap = cap_L)
tr2 = GNNTrainer(g, cfg, tcfg, "comm_rand", seed=3, mesh=mesh)
tr2._hplan = dist_gnn.HaloPlan("halo", 2, tr2.caps[-1])
tr2._hplan_epoch = 0
l2 = tr2.train_steps(8)
assert np.isfinite(l2).all()
print("HALO_MODE_OK")

# with a donating step (the non-CPU default) warmup compiles on copies,
# so the trainer's own params and opt state survive it
import functools
make_steps = dist_gnn.make_sharded_steps
dist_gnn.make_sharded_steps = functools.partial(make_steps, donate=True)
tr3 = GNNTrainer(g, cfg, tcfg, "comm_rand", seed=3, mesh=mesh).warmup()
assert np.isfinite(tr3.train_steps(2)).all()
dist_gnn.make_sharded_steps = make_steps
print("DONATE_OK")

# host mirror == device exchange, element for element
D, Ns, F, K = 4, 8, 5, 12
rng = np.random.default_rng(0)
feats = rng.normal(size=(D, Ns, F)).astype(np.float32)
ids = rng.integers(0, Ns * D + 6, size=(D, K))
def f(fl, il):
    out, drop = halo.halo_gather(fl[0], il[0], n_per_shard=Ns, r_cap=K,
                                 halo=D // 2, axis="shard")
    return out[None], drop[None]
m = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("shard"), P("shard")),
                          out_specs=(P("shard"), P("shard")),
                          check_vma=False))
out_dev, drop_dev = m(jnp.asarray(feats), jnp.asarray(ids))
out_np, drop_np = halo.halo_gather_np(feats, ids, n_per_shard=Ns,
                                      r_cap=K, halo=D // 2)
assert np.array_equal(np.asarray(out_dev), out_np)
assert np.array_equal(np.asarray(drop_dev), drop_np)
print("MIRROR_OK")

# the fused Pallas kernels run under shard_map (interpret mode on CPU)
from repro.kernels.gather_agg.ops import gather_agg
x = jnp.asarray(rng.normal(size=(4, 16, 8)).astype(np.float32))
idx = jnp.asarray(rng.integers(0, 16, size=(4, 6, 3)), jnp.int32)
w = jnp.ones((4, 6, 3), jnp.float32)
def agg(x, idx, w):
    return gather_agg(x[0], idx[0], w[0], impl="pallas")[None]
out = jax.jit(jax.shard_map(agg, mesh=mesh,
                            in_specs=(P("shard"), P("shard"), P("shard")),
                            out_specs=P("shard"), check_vma=False))(x, idx, w)
ref = np.stack([np.asarray(gather_agg(x[i], idx[i], w[i], impl="jnp"))
                for i in range(4)])
np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6)
print("KERNELS_OK")
"""


def _run_sub(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)


def test_four_replica_mesh_subprocess():
    out = _run_sub(FOUR_REPLICA_SCRIPT)
    for marker in ("CONCAT_OK", "CONVERGE_OK", "HALO_MODE_OK",
                   "DONATE_OK", "MIRROR_OK", "KERNELS_OK"):
        assert marker in out.stdout, (marker, out.stdout, out.stderr[-3000:])
