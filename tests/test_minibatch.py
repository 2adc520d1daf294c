"""Static-shape batch builder: dedup exactness, index validity, policy
footprint ordering (the paper's Fig 6 mechanism)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sampling
from repro.configs.base import BASELINE_POLICY, BEST_POLICY, CommRandPolicy
from repro.core import minibatch as mb, partition
from repro.graphs.csr import DeviceGraph


@pytest.fixture(scope="module")
def gdev(tiny_graph):
    return DeviceGraph.from_graph(tiny_graph)


def _build(tiny_graph, gdev, roots, fanouts=(5, 5), caps=(1024, 1536),
           p=0.5, key=0):
    labels = jnp.asarray(tiny_graph.labels)
    return mb.build_batch(jax.random.key(key), gdev,
                          jnp.asarray(roots, jnp.int32), labels,
                          fanouts, caps, p)


def test_levels_are_sorted_unique_supersets(tiny_graph, gdev):
    roots = tiny_graph.train_ids[:128]
    b = _build(tiny_graph, gdev, roots)
    N = tiny_graph.num_nodes
    prev = None
    for lvl in b.levels:
        arr = np.asarray(lvl)
        real = arr[arr < N]
        assert (np.diff(arr) >= 0).all()
        assert len(np.unique(real)) == len(real)
        if prev is not None:
            assert set(prev) <= set(real)
        prev = real


def test_block_positions_consistent(tiny_graph, gdev):
    roots = tiny_graph.train_ids[:128]
    b = _build(tiny_graph, gdev, roots)
    L = len(b.blocks)
    for i, blk in enumerate(b.blocks):
        src_level = np.asarray(b.levels[L - i])
        dst_level = np.asarray(b.levels[L - i - 1])
        sp = np.asarray(blk.self_pos)
        ok = np.asarray(blk.dst_mask)
        assert (src_level[sp[ok]] == dst_level[ok]).all()
        em = np.asarray(blk.edge_mask)
        srcs = src_level[np.asarray(blk.src_pos)]
        assert (srcs[em] < tiny_graph.num_nodes).all()


def test_labels_align_with_roots(tiny_graph, gdev):
    roots = tiny_graph.train_ids[:64]
    b = _build(tiny_graph, gdev, np.pad(roots, (0, 64), constant_values=-1))
    lm = np.asarray(b.label_mask)
    lv = np.asarray(b.levels[0])
    lab = np.asarray(b.labels)
    assert lm.sum() == 64
    assert (lab[lm] == tiny_graph.labels[lv[lm]]).all()


def test_footprint_ordering_across_policies(tiny_graph, gdev):
    """Unique input nodes: RAND p=.5 > COMM-RAND p=1 > NORAND p=1 (Fig 6)."""
    rng = np.random.default_rng(0)
    sizes = {}
    for name, pol in [("rand", BASELINE_POLICY),
                      ("best", BEST_POLICY),
                      ("norand", CommRandPolicy("norand", 0.0, 1.0))]:
        batches = partition.batches_for_epoch(
            tiny_graph.train_ids, tiny_graph.communities, pol, 256, rng)
        caps = (2048, 2048)
        tot = []
        for k, b in enumerate(batches[:4]):
            bb = _build(tiny_graph, gdev, b, caps=caps, p=pol.p, key=k)
            tot.append(int(bb.num_unique))
        sizes[name] = np.mean(tot)
    assert sizes["norand"] <= sizes["best"] < sizes["rand"]


def test_capacity_overflow_degrades_gracefully(tiny_graph, gdev):
    roots = tiny_graph.train_ids[:256]
    tight = _build(tiny_graph, gdev, roots, caps=(320, 384))
    assert int(tight.num_unique) <= 384
    for blk in tight.blocks:
        assert np.asarray(blk.edge_mask).dtype == np.bool_


def test_calibrated_caps_hold(tiny_graph, gdev):
    pol = BEST_POLICY
    caps = mb.calibrate_caps(tiny_graph, pol, 128, (5, 5), n_probe=4)
    rng = np.random.default_rng(3)
    batches = partition.batches_for_epoch(
        tiny_graph.train_ids, tiny_graph.communities, pol, 128, rng)
    b = _build(tiny_graph, gdev, batches[0], caps=caps, p=pol.p)
    N = tiny_graph.num_nodes
    # no silent drops: every sampled edge lands
    for blk in b.blocks:
        em = np.asarray(blk.edge_mask)
        dm = np.asarray(blk.dst_mask)
        assert em[dm].any(axis=1).mean() > 0.99


_SCOPE = re.compile(r"^jit\(_build_batch\)/build/"
                    r"(roots|labels|hop(\d+)/(sample|dedup|positions))/")
_OPCODE = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = .*? (fusion|while|sort)\(")


@pytest.mark.parametrize("sampler", ["biased", "labor"])
def test_build_phases_carry_named_scopes(tiny_graph, gdev, sampler):
    """Every fusion, while and sort of the compiled build that carries an
    op name sits under one build scope, and each hop shows its sample,
    dedup and positions scope (the compiler's own rewrites, such as the
    cumsum split, carry no op name at all and are not counted)."""
    roots = jnp.asarray(tiny_graph.train_ids[:64], jnp.int32)
    fanouts, caps = (5, 5), (1024, 1536)
    txt = mb._build_batch.lower(
        jax.random.key(0), jax.random.key(1), gdev, roots,
        jnp.asarray(tiny_graph.labels), fanouts, caps,
        sampling.resolve(sampler)).compile().as_text()
    seen, outside, whiles = set(), [], []
    for line in txt.splitlines():
        op = _OPCODE.match(line)
        name = re.search(r'op_name="([^"]*)"', line)
        if op is None or name is None:
            continue
        m = _SCOPE.match(name.group(1))
        if m is None:
            outside.append(line.strip()[:120])
            continue
        seen.add(m.group(1))
        if op.group(1) == "while":
            whiles.append(m.group(1))
    assert outside == []
    hops = {f"hop{h}/{p}" for h in range(len(fanouts))
            for p in ("sample", "dedup", "positions")}
    assert hops <= seen
    # the searchsorted loops are the position maps' and the labels' (the
    # others are the samplers' key splits)
    assert {f"hop{h}/positions" for h in range(len(fanouts))} <= set(whiles)
    assert all(w.endswith(("/positions", "/sample", "labels"))
               for w in whiles)
