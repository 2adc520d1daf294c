"""The benchmark's graph generator: deterministic, shaped as its spec,
and within 5% of the spec's directed edge count."""
import numpy as np
import pytest

from chipbench import graphgen

SPEC = graphgen.GraphSpec(
    name="small", num_nodes=6000, num_edges=200_000, feat_dim=12,
    num_classes=7, split=(3000, 1000, 2000), num_communities=20,
    p_intra=0.9, seed=3)


@pytest.fixture(scope="module")
def graph():
    return graphgen.generate(SPEC)


def test_same_spec_same_graph(graph):
    again = graphgen.generate(SPEC)
    for k in ("indptr", "indices", "labels", "communities", "train_ids",
              "val_ids", "test_ids"):
        assert np.array_equal(getattr(graph, k), getattr(again, k)), k
    other = graphgen.generate(graphgen.GraphSpec(
        **{**SPEC.__dict__, "seed": 4}))
    assert not np.array_equal(graph.indices[:1000], other.indices[:1000])


def test_shapes_match_spec(graph):
    n = SPEC.num_nodes
    assert graph.indptr.shape == (n + 1,)
    assert graph.indptr[-1] == len(graph.indices)
    assert graph.labels.shape == graph.communities.shape == (n,)
    assert set(np.unique(graph.labels)) == set(range(SPEC.num_classes))
    assert graph.communities.max() + 1 == SPEC.num_communities
    assert (len(graph.train_ids), len(graph.val_ids),
            len(graph.test_ids)) == SPEC.split
    ids = np.concatenate([graph.train_ids, graph.val_ids, graph.test_ids])
    assert np.array_equal(np.sort(ids), np.arange(n))


def test_edge_count_within_5_percent(graph):
    assert abs(len(graph.indices) - SPEC.num_edges) <= 0.05 * SPEC.num_edges


def test_simple_symmetric_graph(graph):
    n = SPEC.num_nodes
    src = np.repeat(np.arange(n), np.diff(graph.indptr))
    dst = graph.indices.astype(np.int64)
    assert not np.any(src == dst)
    fwd = src * n + dst
    assert np.all(np.diff(fwd) > 0)         # rows sorted, no duplicates
    assert np.array_equal(np.sort(dst * n + src), fwd)
    intra = graph.communities[src] == graph.communities[dst]
    assert 0.85 < intra.mean() < 0.95       # p_intra = 0.9


def test_features_from_seed():
    labels = np.array([0, 1, 1, 6], np.int32)
    comms = np.array([0, 3, 3, 19], np.int32)
    a = np.asarray(graphgen.features(SPEC, labels, comms))
    b = np.asarray(graphgen.features(SPEC, labels, comms))
    assert a.shape == (4, SPEC.feat_dim) and a.dtype == np.float32
    assert np.array_equal(a, b)


def test_equal_community_sizes():
    spec = graphgen.GraphSpec(**{**SPEC.__dict__, "num_nodes": 6003,
                                 "split": (3003, 1000, 2000),
                                 "community_size_skew": 0})
    sizes = np.bincount(graphgen.generate(spec).communities)
    assert len(sizes) == spec.num_communities
    assert sizes.sum() == 6003 and set(sizes) == {300, 301}
