"""Seeded degree-corrected SBM graphs at a published dataset's shape.

The benchmark's data is its own: this generator is a vectorized copy of
the program's `repro.graphs.synthetic` generator, so a change to the
program's generator never moves the graphs the benchmark measures on.

A spec (one JSON file under `chipbench/graphs/`) fixes the node, edge,
feature, class and split counts of the published dataset, plus the
`assumed` generator knobs (community count, intra-community share of
edges, degree law, label and feature noise, seed). The graph is emitted in
random node order, like a raw dataset; `chipbench/dataset.py` runs the
program's community reordering on it.

Edges are undirected pairs drawn until, after dedup and symmetrization,
the directed edge count lands within 1% of the spec's `num_edges` (top-up
rounds replace the pairs that dedup removes). Features are not made here:
`features()` makes them on the device from the spec's seed.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict

import numpy as np

EDGE_TOL = 0.01          # generation target; the spec's promise is 5%
MAX_ROUNDS = 8


@dataclass(frozen=True)
class GraphSpec:
    name: str
    num_nodes: int
    num_edges: int          # directed (each undirected edge counted twice)
    feat_dim: int
    num_classes: int
    split: tuple            # (train, val, test) counts
    num_communities: int
    p_intra: float          # share of edges drawn inside a community
    community_size_skew: float = 1.3    # Pareto shape; 0: equal sizes
    degree_pareto_a: float = 2.0
    label_noise: float = 0.1
    feat_noise: float = 1.0
    seed: int = 0

    @staticmethod
    def from_dict(name: str, d: Dict) -> "GraphSpec":
        a = d["assumed"]
        return GraphSpec(
            name=name, num_nodes=int(d["num_nodes"]),
            num_edges=int(d["num_edges"]), feat_dim=int(d["feat_dim"]),
            num_classes=int(d["num_classes"]),
            split=tuple(int(x) for x in d["split"]),
            num_communities=int(a["num_communities"]),
            p_intra=float(a["p_intra"]),
            community_size_skew=float(a["community_size_skew"]),
            degree_pareto_a=float(a["degree_pareto_a"]),
            label_noise=float(a["label_noise"]),
            feat_noise=float(a["feat_noise"]),
            seed=int(a["seed"]))

    def key(self) -> str:
        """Stable identity of the generated data (cache key)."""
        return json.dumps(self.__dict__, sort_keys=True)


@dataclass
class RawGraph:
    """Host arrays of a generated graph, node ids in random order."""
    indptr: np.ndarray       # (N+1,) int64
    indices: np.ndarray      # (E,) int32, rows sorted ascending
    labels: np.ndarray       # (N,) int32
    communities: np.ndarray  # (N,) int32
    train_ids: np.ndarray    # sorted int64
    val_ids: np.ndarray
    test_ids: np.ndarray


def _community_sizes(rng, spec: GraphSpec) -> np.ndarray:
    """Community sizes summing to `num_nodes`: equal (to one node) when
    `community_size_skew` is 0, else Pareto-distributed with that shape."""
    if spec.community_size_skew == 0:
        q, r = divmod(spec.num_nodes, spec.num_communities)
        return q + (np.arange(spec.num_communities) < r).astype(np.int64)
    w = rng.pareto(spec.community_size_skew, spec.num_communities) + 1.0
    sizes = np.maximum((w / w.sum() * spec.num_nodes).astype(np.int64), 8)
    sizes[np.argmax(sizes)] += spec.num_nodes - sizes.sum()
    return sizes


def _draw_pairs(rng, n_pairs, spec, comm_cum, comm_start, node_cum,
                by_comm, theta_cum_all):
    """n_pairs theta-weighted endpoint pairs; a p_intra share of them
    inside one community (chosen by its theta mass)."""
    n_intra = int(round(n_pairs * spec.p_intra))
    n_inter = n_pairs - n_intra
    # intra: community by mass, then both endpoints by theta inside it
    c = np.searchsorted(comm_cum, rng.random(n_intra) * comm_cum[-1],
                        side="right")
    lo = np.where(c > 0, comm_cum[np.maximum(c - 1, 0)], 0.0)
    mass = comm_cum[c] - lo
    ends = []
    for _ in range(2):
        t = lo + rng.random(n_intra) * mass
        pos = np.searchsorted(node_cum, t, side="right")
        # rounding at a community's right edge must not leave it
        last = comm_start[c + 1] - 1
        pos = np.clip(pos, comm_start[c], last)
        ends.append(by_comm[pos])
    # inter: both endpoints by theta over the whole graph
    n = len(theta_cum_all)
    s = np.minimum(np.searchsorted(
        theta_cum_all, rng.random(n_inter) * theta_cum_all[-1],
        side="right"), n - 1)
    d = np.minimum(np.searchsorted(
        theta_cum_all, rng.random(n_inter) * theta_cum_all[-1],
        side="right"), n - 1)
    return (np.concatenate([ends[0], s]).astype(np.int64),
            np.concatenate([ends[1], d]).astype(np.int64))


def _undirected_keys(u, v, n):
    keep = u != v
    u, v = u[keep], v[keep]
    return np.unique(np.minimum(u, v) * n + np.maximum(u, v))


def generate(spec: GraphSpec) -> RawGraph:
    rng = np.random.default_rng((spec.seed, 0))
    N, C = spec.num_nodes, spec.num_communities
    sizes = _community_sizes(rng, spec)
    comm_of = np.repeat(np.arange(C, dtype=np.int32), sizes)
    comm_of = comm_of[rng.permutation(N)]       # raw datasets are unsorted
    theta = rng.pareto(spec.degree_pareto_a, N) + 1.0

    by_comm = np.argsort(comm_of, kind="stable")
    comm_start = np.zeros(C + 1, np.int64)
    np.cumsum(np.bincount(comm_of, minlength=C), out=comm_start[1:])
    node_cum = np.cumsum(theta[by_comm])
    comm_cum = node_cum[comm_start[1:] - 1]
    theta_cum_all = np.cumsum(theta)

    target = spec.num_edges // 2
    keys = np.zeros(0, np.int64)
    draw = target
    for _ in range(MAX_ROUNDS):
        u, v = _draw_pairs(rng, draw, spec, comm_cum, comm_start, node_cum,
                           by_comm, theta_cum_all)
        before = len(keys)
        keys = np.union1d(keys, _undirected_keys(u, v, N))
        missing = target - len(keys)
        if abs(missing) <= EDGE_TOL * target:
            break
        if missing < 0:
            # overshoot: drop a seeded random subset of the surplus
            drop = rng.choice(len(keys), -missing, replace=False)
            keys = np.delete(keys, drop)
            break
        kept = max(len(keys) - before, 1) / draw
        draw = int(np.ceil(missing / kept))
    lo, hi = keys // N, keys % N
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    order = np.argsort(src * N + dst)
    src, dst = src[order], dst[order]
    indptr = np.zeros(N + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=N), out=indptr[1:])
    indices = dst.astype(np.int32)

    # labels: communities map onto classes (every class used when C >= K),
    # plus uniform label noise
    K = spec.num_classes
    class_of_comm = (rng.permutation(C) % K).astype(np.int32)
    labels = class_of_comm[comm_of]
    flip = rng.random(N) < spec.label_noise
    labels[flip] = rng.integers(0, K, int(flip.sum()))

    n_tr, n_va, n_te = spec.split
    if n_tr + n_va + n_te != N:
        raise ValueError(f"{spec.name}: split {spec.split} does not sum "
                         f"to {N} nodes")
    perm = rng.permutation(N)
    return RawGraph(
        indptr=indptr, indices=indices, labels=labels.astype(np.int32),
        communities=comm_of,
        train_ids=np.sort(perm[:n_tr]).astype(np.int64),
        val_ids=np.sort(perm[n_tr:n_tr + n_va]).astype(np.int64),
        test_ids=np.sort(perm[n_tr + n_va:]).astype(np.int64))


def features(spec: GraphSpec, labels, communities):
    """(N, feat_dim) float32 features on the default device, made in one
    jitted call from the spec's seed: a class centroid, a community
    offset and Gaussian noise. `labels`/`communities` are in the node
    order the features are for (after any relabeling)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, labels, communities):
        k1, k2, k3 = jax.random.split(key, 3)
        class_mu = jax.random.normal(k1, (spec.num_classes, spec.feat_dim))
        comm_mu = 0.5 * jax.random.normal(
            k2, (spec.num_communities, spec.feat_dim))
        noise = spec.feat_noise * jax.random.normal(
            k3, (labels.shape[0], spec.feat_dim))
        return class_mu[labels] + comm_mu[communities] + noise

    return make(jax.random.key(spec.seed), jnp.asarray(labels, jnp.int32),
                jnp.asarray(communities, jnp.int32))
