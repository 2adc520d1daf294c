"""Data-parallel GNN training on a community-partitioned device mesh.

COMM-RAND's community structure is the sharding key: the (N, F) feature
matrix is partitioned so every community lives wholly inside one shard
(communities <-> shards), each replica consumes a slice of the ONE global
counter-based epoch order, and cross-shard neighbor features move through
`core.halo` ring exchanges planned per epoch from that order. Gradients
are `psum`-reduced inside the jitted `shard_map` step, so D replicas
train one model.

Determinism contract (what the tests pin):

  * the global root order is the single source of truth — replica r's
    roots for global batch `pos` are `order[pos][r*Bs:(r+1)*Bs]`, so the
    per-replica streams CONCATENATE to the exact single-device epoch
    order, and `Cursor(epoch, pos)` semantics (checkpoint/resume) are
    unchanged;
  * every replica builds its sub-batch with the SAME `(seed, epoch,
    pos)`-derived key (the cooperative-minibatching choice: shared
    sampling randomness across replicas, arXiv:2310.12403);
  * the sharded loss is `sum_r nll_r / max(psum(mask_r), 1)` — at D=1
    every collective is an identity, so a 1-replica mesh run is
    BIT-identical to the single-device `train_step` (loss trajectory and
    params digest, asserted by tests/test_dist_gnn.py);
  * halo-gathered rows are bit-copies of the global feature rows (the
    partition is a relabeling, `ShardPlan.shard_pos` a bijection), so
    sharding never perturbs the numerics of a feature read.

Halo planning: `plan_halo` computes, from the epoch's root slices and
the graph's shard-adjacency reachability (an over-approximation of any
L-hop sampled neighborhood, so the budget is always sufficient), the
ring distance each replica needs; `r_cap = cap_L` makes the exchange
provably dropless (one replica requests at most cap_L rows total, so no
single neighbor can see more). When the predicted halo bytes exceed the
all-gather fallback, the plan degrades to `mode="global"`. Plans are
frozen dataclasses: `GNNTrainer` re-plans at epoch boundaries and reuses
the jitted step whenever the plan is unchanged (the recompile-stability
contract `analysis.jaxpr_audit.audit_sharded_step` gates).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.batching.stream import BatchStream
from repro.core import halo
from repro.core import minibatch as mb
from repro.graphs.csr import Graph

AXIS = "shard"


def make_gnn_mesh(n_shards: Optional[int] = None) -> Mesh:
    """1-D ("shard",) mesh over the first `n_shards` devices (default:
    all). CI simulates multi-host with
    XLA_FLAGS=--xla_force_host_platform_device_count=4."""
    devs = jax.devices()
    n = len(devs) if n_shards is None else n_shards
    if len(devs) < n:
        raise RuntimeError(f"mesh needs {n} devices, found {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (AXIS,))


# ---------------------------------------------------------------------------
# community-aligned feature partition
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShardPlan:
    """Community-aligned node partition for a D-shard mesh.

    `shard_pos` is a BIJECTION from global node ids onto distinct slots
    of the padded (D * n_per_shard) local-slot space: node i lives at
    local slot `shard_pos[i] - owner*n_per_shard` of shard
    `owner = shard_pos[i] // n_per_shard`. `perm` inverts it
    (`perm[shard_pos[i]] == i`; padding slots hold -1). Communities are
    never split across shards, so COMM-RAND's community-pure batches
    keep their feature reads shard-local."""
    n_shards: int
    n_nodes: int
    n_per_shard: int
    shard_pos: np.ndarray        # (N,) int32 global id -> padded slot
    perm: np.ndarray             # (D * n_per_shard,) int64 slot -> id | -1
    shard_of_comm: np.ndarray    # (n_comm,) int32

    @property
    def n_padded(self) -> int:
        return self.n_shards * self.n_per_shard

    @property
    def shard_of_node(self) -> np.ndarray:
        return (self.shard_pos // self.n_per_shard).astype(np.int32)

    def shard_features(self, features: np.ndarray, mesh: Mesh):
        """Pad + permute the (N, F) matrix into its (D * Ns, F) sharded
        layout (padding slots are zero rows) and device_put it
        P("shard", None). Rows are bit-copies: `local[shard_pos[i]] ==
        features[i]` exactly."""
        feats = np.asarray(features)
        out = np.zeros((self.n_padded, feats.shape[1]), feats.dtype)
        valid = self.perm >= 0
        out[valid] = feats[self.perm[valid]]
        return jax.device_put(
            jnp.asarray(out), NamedSharding(mesh, P(AXIS, None)))

    def device_pos(self, mesh: Mesh):
        """The (N,) id->slot map, replicated (rides into the jitted
        sharded step as an argument, never a baked constant)."""
        return jax.device_put(
            jnp.asarray(self.shard_pos, jnp.int32),
            NamedSharding(mesh, P()))


def community_shard_plan(graph: Graph, n_shards: int) -> ShardPlan:
    """Greedy balanced assignment of whole communities to shards.

    Communities are sorted by size (largest first) and dealt to the
    least-loaded shard; within a shard, nodes keep ascending global-id
    order (after `core.reorder.prepare` that is the community-contiguous
    degree order). D=1 degenerates to the identity relabeling."""
    if graph.communities is None:
        raise ValueError("graph has no communities — run "
                         "core.reorder.prepare first")
    comm = np.asarray(graph.communities, np.int64)
    n_comm = int(comm.max()) + 1 if len(comm) else 0
    sizes = np.bincount(comm, minlength=n_comm)
    shard_of_comm = np.zeros(n_comm, np.int32)
    load = np.zeros(n_shards, np.int64)
    for c in np.argsort(-sizes, kind="stable"):
        s = int(np.argmin(load))
        shard_of_comm[c] = s
        load[s] += sizes[c]
    n_per_shard = int(load.max()) if n_shards > 1 else graph.num_nodes
    owner = shard_of_comm[comm]
    shard_pos = np.zeros(graph.num_nodes, np.int32)
    perm = np.full(n_shards * n_per_shard, -1, np.int64)
    for s in range(n_shards):
        ids = np.nonzero(owner == s)[0]          # ascending global ids
        slots = s * n_per_shard + np.arange(len(ids))
        shard_pos[ids] = slots
        perm[slots] = ids
    return ShardPlan(n_shards, graph.num_nodes, n_per_shard,
                     shard_pos, perm, shard_of_comm)


# ---------------------------------------------------------------------------
# per-epoch halo planning
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class HaloPlan:
    """Static exchange budget for one epoch's sharded feature gathers.
    Frozen + hashable: the jitted step is cached per plan, so epochs
    with identical plans never retrace."""
    mode: str                    # "halo" | "global"
    halo: int                    # ring distance (0 at D=1)
    r_cap: int                   # request slots per neighbor (= cap_L)

    def bytes_per_gather(self, cap_l: int, feat_dim: int,
                         n_shards: int) -> int:
        return halo.collective_bytes_model(
            cap_l, feat_dim, n_shards, self.r_cap, self.halo, self.mode)


def _ring_dist(a: np.ndarray, b: np.ndarray, d: int) -> np.ndarray:
    fwd = (a - b) % d
    return np.minimum(fwd, d - fwd)


def shard_adjacency(graph: Graph, plan: ShardPlan) -> np.ndarray:
    """(D, D) bool: shard s has an edge into shard t — the 1-hop
    over-approximation any sampled neighborhood is a subset of."""
    d = plan.n_shards
    owner = plan.shard_of_node
    src = np.repeat(np.arange(graph.num_nodes),
                    np.diff(graph.indptr).astype(np.int64))
    adj = np.zeros((d, d), bool)
    adj[owner[src], owner[graph.indices]] = True
    adj |= np.eye(d, dtype=bool)
    return adj


def plan_halo(plan: ShardPlan, graph: Graph, fanouts, cap_l: int,
              root_batches: Optional[np.ndarray] = None,
              mode: str = "auto") -> HaloPlan:
    """Pick (mode, halo, r_cap) for one epoch.

    `root_batches` is the epoch's (n_batches, B) global root order (from
    `ShardedBatchStream.root_batches`); each replica's required ring
    distance is the max distance from ITS index to any shard reachable
    in L hops from the owner shards of ITS root slices. None plans for
    the worst case (all shards rooted everywhere). `r_cap = cap_l` makes
    the halo exchange dropless by construction: a replica requests at
    most cap_l rows total, so no one neighbor can be asked for more."""
    d = plan.n_shards
    if d == 1:
        return HaloPlan("halo", 0, cap_l)
    reach = shard_adjacency(graph, plan)
    hops = np.eye(d, dtype=bool)
    for _ in range(len(fanouts)):
        hops = hops @ reach
    owner = plan.shard_of_node
    need = 0
    if root_batches is None:
        rooted = np.ones((d, d), bool)           # replica r roots anywhere
    else:
        rb = np.asarray(root_batches)
        bs = rb.shape[1] // d
        rooted = np.zeros((d, d), bool)
        for r in range(d):
            roots = rb[:, r * bs:(r + 1) * bs].reshape(-1)
            roots = roots[roots >= 0]
            rooted[r, np.unique(owner[roots])] = True
    targets = rooted @ hops                      # (replica, owner-shard)
    for r in range(d):
        ts = np.nonzero(targets[r])[0]
        if len(ts):
            need = max(need, int(_ring_dist(np.full(len(ts), r), ts,
                                            d).max()))
    hp = HaloPlan("halo", need, cap_l)
    if mode == "auto":
        if hp.bytes_per_gather(cap_l, graph.feat_dim, d) > \
                HaloPlan("global", 0, 0).bytes_per_gather(
                    cap_l, graph.feat_dim, d):
            hp = HaloPlan("global", 0, 0)
    elif mode == "global":
        hp = HaloPlan("global", 0, 0)
    return hp


# ---------------------------------------------------------------------------
# sharded batch stream: D sub-batches from ONE global order
# ---------------------------------------------------------------------------
class ShardedBatchStream(BatchStream):
    """`BatchStream` whose compiled batches carry a leading shard axis.

    The epoch order, `num_batches`, cursor and key derivations are the
    base class's — bit-identical to single-device. Only `build` changes:
    the (B,) global root batch is dealt as D contiguous (B/D,) slices
    (slice r -> replica r), each built through the SAME shape-generic
    `_build_batch` with the SAME (epoch, pos) key, and the D sub-batch
    pytrees are stacked and device_put P("shard", ...). Concatenating
    the replica slices reconstructs the global order exactly
    (`replica_root_batches`)."""

    def __init__(self, *args, mesh: Mesh, plan: ShardPlan, **kwargs):
        super().__init__(*args, **kwargs)
        if self.batch_size % plan.n_shards:
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by "
                f"{plan.n_shards} shards")
        self.mesh = mesh
        self.plan = plan
        self._batch_sharding = NamedSharding(mesh, P(AXIS))

    def replica_root_batches(self, epoch: int) -> np.ndarray:
        """(n_batches, D, B/D) per-replica root slices; concatenated
        over the replica axis they equal `root_batches(epoch)`."""
        rb = self.root_batches(epoch)
        d = self.plan.n_shards
        return rb.reshape(rb.shape[0], d, self.batch_size // d)

    def build(self, roots: np.ndarray, epoch: int, pos: int) -> mb.MiniBatch:
        d = self.plan.n_shards
        bs = self.batch_size // d
        key = self.batch_key(epoch, pos)
        ekey = self.epoch_key(epoch)
        ctx = self.epoch_ctx(epoch)
        subs = [mb._build_batch(
            key, ekey, self.g,
            jnp.asarray(roots[r * bs:(r + 1) * bs], jnp.int32),
            self.labels, self.fanouts, self.caps, self.sampler, ctx)
            for r in range(d)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *subs)
        return jax.tree.map(
            lambda x: jax.device_put(x, self._batch_sharding), stacked)


# ---------------------------------------------------------------------------
# the jitted sharded step
# ---------------------------------------------------------------------------
def gather_batch_features(feats_local, shard_pos, ids, plan: ShardPlan,
                          hplan: HaloPlan, cache=None, axis: str = AXIS):
    """Inside shard_map: serve `ids` (global node ids, sentinel >= N ->
    zero rows) as exact bit-copies of the global feature rows — cache
    hits from the replicated cache rows, everything else through the
    planned `core.halo` exchange on the remapped shard-slot ids.
    Returns ((K, F) rows, dropped count)."""
    n, npad = plan.n_nodes, plan.n_padded
    valid = ids < n
    cid = jnp.minimum(ids, n - 1)
    rid = jnp.where(valid, shard_pos[cid], npad)
    cpos = None
    if cache is not None:
        cpos = cache.pos[cid]
        hit = valid & (cpos >= 0)
        rid = jnp.where(hit, npad, rid)          # hits stay off the wire
    rows, dropped = halo.gather_for_policy(
        feats_local, rid, n_per_shard=plan.n_per_shard,
        r_cap=hplan.r_cap, halo=hplan.halo, axis=axis, mode=hplan.mode)
    if cache is not None:
        crow = cache.cache[jnp.maximum(cpos, 0)]
        rows = jnp.where(hit[:, None], crow, rows)
    return rows, dropped


def sharded_softmax_ce(logits, labels, mask, axis: str = AXIS):
    """`train.losses.gnn_softmax_ce` with the mask count psum-reduced:
    the per-replica value is this replica's share of the GLOBAL masked
    mean, so `psum(loss_r)` equals the single-device loss and
    `psum(grad_r)` its gradient. At D=1 the psum is an identity and the
    expression is bit-for-bit `gnn_softmax_ce`."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[:, None], axis=-1)[:, 0]
    nll = (lse - picked) * mask
    return nll.sum() / jnp.maximum(lax.psum(mask.sum(), axis), 1.0)


def make_sharded_steps(cfg, tcfg, mesh: Mesh, plan: ShardPlan,
                       hplan: HaloPlan, *, donate: Optional[bool] = None,
                       axis: str = AXIS):
    """Build the jitted data-parallel train step.

    Same 10-argument signature as the single-device
    `train.gnn_loop._make_steps` train step, with two layout changes the
    trainer owns: `batch` leaves carry a leading shard axis
    (`ShardedBatchStream`), and `feats` is the dict
    {"local": (D*Ns, F) P("shard", None), "pos": (N,) replicated}. The
    dropout key is passed as raw `jax.random.key_data` bits (wrapped
    back inside the step) so PRNG-key dtypes never meet shard_map specs.
    Returns `(params, opt, loss, ok, skips, hits, misses, aux)` where
    `aux` is a per-replica dict (leaves shaped (D,)): per-replica loss
    share, halo-dropped count, cache hit/miss counters — the per-replica
    observability feed. `hplan` is static: one compiled step per plan."""
    from repro.featcache.plan import CachePlan
    from repro.kernels.gather_cached.ops import cache_stats
    from repro.models.gnn.models import apply_gnn
    from repro.optim import adamw

    if donate is None:
        donate = jax.default_backend() != "cpu"
    n = plan.n_nodes

    def per_replica(params, opt_state, batch, feats, degrees, lr,
                    key_data, cache, poison, skips):
        b = jax.tree.map(lambda x: x[0], batch)  # strip the shard axis
        key = jax.random.wrap_key_data(key_data)
        rows, dropped = gather_batch_features(
            feats["local"], feats["pos"], b.node_ids, plan, hplan,
            cache=cache, axis=axis)

        def loss_fn(p):
            # apply_gnn masks the table by node_mask itself; rows at
            # invalid (sentinel) positions are already zero
            logits = apply_gnn(cfg, p, b, rows, degrees, train=True,
                               dropout_key=key, feats_global=False,
                               cache=None)
            return sharded_softmax_ce(
                logits, b.labels, b.label_mask.astype(jnp.float32),
                axis) * poison

        loss_r, grads_r = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree.map(lambda g: lax.psum(g, axis), grads_r)
        loss = lax.psum(loss_r, axis)
        # in-jit guard (repro.resilience): grads are psum'd, so the
        # verdict — and the where-select below — is identical on every
        # replica; no replica can diverge from the others' params
        ok = jnp.isfinite(loss)
        for g in jax.tree.leaves(grads):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
        new_params, new_opt = adamw.update(
            grads, opt_state, params, lr=lr,
            weight_decay=tcfg.weight_decay)

        def keep(new, old):
            return jax.tree.map(lambda a, o: jnp.where(ok, a, o), new, old)

        new_params = keep(new_params, params)
        new_opt = keep(new_opt, opt_state)
        skips = jnp.where(ok, jnp.int32(0), skips + jnp.int32(1))
        if cache is not None:
            h_r, m_r = cache_stats(cache.pos, b.node_ids, n)
        else:
            h_r = m_r = jnp.int32(0)
        hits = lax.psum(h_r, axis)
        misses = lax.psum(m_r, axis)
        aux = {"loss": loss_r[None], "dropped": dropped[None],
               "hits": h_r[None], "misses": m_r[None]}
        return (new_params, new_opt, loss, ok, skips, hits, misses, aux)

    rep, sh = P(), P(axis)
    feats_spec = {"local": P(axis, None), "pos": rep}
    in_specs = (rep, rep, sh, feats_spec, rep, rep, rep, rep, rep, rep)
    out_specs = (rep, rep, rep, rep, rep, rep, rep,
                 {"loss": sh, "dropped": sh, "hits": sh, "misses": sh})
    mapped = jax.shard_map(per_replica, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    step = jax.jit(mapped, donate_argnums=(0, 1) if donate else ())

    def train_step(params, opt_state, batch, feats, degrees, lr, key,
                   cache, poison, skips):
        if cache is not None and not isinstance(cache, CachePlan):
            raise ValueError(
                "sharded training supports a static CachePlan only "
                f"(got {type(cache).__name__}); dynamic admission is a "
                "single-device feature for now")
        return step(params, opt_state, batch, feats, degrees, lr,
                    jax.random.key_data(key), cache, poison, skips)

    train_step.mapped = mapped        # undonated: what the audit traces
    return train_step


def replicate(tree, mesh: Mesh):
    """device_put every leaf fully replicated on the mesh."""
    s = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, s), tree)


def state_shardings(state, mesh: Mesh):
    """Replicated NamedSharding tree for a checkpoint state dict — what
    `train.checkpoint.restore(..., shardings=)` device_puts restored
    leaves with (sharded resume)."""
    s = NamedSharding(mesh, P())
    return jax.tree.map(lambda _: s, state)


# ---------------------------------------------------------------------------
# per-replica observability (distinct Perfetto pid per replica)
# ---------------------------------------------------------------------------
class ReplicaTraceEmitter:
    """Re-emit the lockstep step schedule as one Perfetto track per
    replica (`Tracer.for_replica` pid views), fed from the sharded
    step's per-replica aux outputs.

    The SPMD step is dispatched once for all replicas, so each replica's
    step intervals are the host dispatch intervals; what distinguishes
    the tracks is the per-replica payload (loss share, halo drops, cache
    counters). `note` records host timestamps only (never syncs);
    `flush` is called at the trainer's existing epoch boundary AFTER its
    drain, converts the accumulated aux (one small host transfer of
    already-computed (D,) arrays) and emits per-replica "train_step"
    spans plus the boundary "epoch_flush" sync span — placed so every
    replica's trace passes the per-pid mid-epoch-sync gate exactly when
    the host trace does."""

    def __init__(self, n_replicas: int, hplan: HaloPlan, cap_l: int,
                 feat_dim: int):
        self.n = n_replicas
        self._steps = []            # (ts_us, dur_us, step)
        self._aux = []
        self._halo_bytes = hplan.bytes_per_gather(
            cap_l, feat_dim, n_replicas)

    def note(self, ts_us: float, dur_us: float, step: int, aux) -> None:
        self._steps.append((ts_us, dur_us, step))
        self._aux.append(aux)

    def flush(self, tracer, epoch) -> None:
        steps, self._steps = self._steps, []
        aux, self._aux = self._aux, []
        if tracer is None or not steps:
            return
        loss = np.stack([np.asarray(a["loss"]) for a in aux])    # (n, D)
        drop = np.stack([np.asarray(a["dropped"]) for a in aux])
        hits = np.stack([np.asarray(a["hits"]) for a in aux])
        miss = np.stack([np.asarray(a["misses"]) for a in aux])
        t0 = steps[0][0]
        end = max(ts + dur for ts, dur, _ in steps)
        for r in range(self.n):
            v = tracer.for_replica(r)
            for (ts, dur, step), l in zip(steps, loss[:, r]):
                v.emit_span("train_step", "step", ts, dur,
                            step=step, loss_share=float(l))
            v.emit_span("epoch", "loop", t0, end - t0 + 1.0, epoch=epoch)
            v.emit_span("epoch_flush", "sync", end, 1.0, epoch=epoch,
                        n_steps=len(steps))
            v.instant("replica_rollup", cat="device", epoch=epoch,
                      n_steps=len(steps),
                      loss_share=float(loss[:, r].sum()),
                      halo_dropped=int(drop[:, r].sum()),
                      halo_bytes=int(self._halo_bytes * len(steps)),
                      cache_hits=int(hits[:, r].sum()),
                      cache_misses=int(miss[:, r].sum()))


__all__ = [
    "AXIS", "HaloPlan", "ReplicaTraceEmitter", "ShardPlan",
    "ShardedBatchStream", "community_shard_plan", "gather_batch_features",
    "make_gnn_mesh", "make_sharded_steps", "plan_halo", "replicate",
    "shard_adjacency", "sharded_softmax_ce", "state_shardings",
]
