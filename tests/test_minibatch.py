"""Static-shape batch builder: dedup exactness, index validity, policy
footprint ordering (the paper's Fig 6 mechanism)."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sampling
from repro.batching import BatchStream, make_policy
from repro.configs.base import BASELINE_POLICY, BEST_POLICY, CommRandPolicy
from repro.core import minibatch as mb, partition
from repro.graphs.csr import DeviceGraph
from repro.pipeline import DeviceBatchBuilder


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
    # the position maps come from the dedup's sort, with no search loop:
    # the loops left are the samplers' key splits and the labels' search
    assert not any(w.endswith("/positions") for w in whiles)
    assert all(w.endswith(("/sample", "labels")) for w in whiles)


# ---------------------------------------------------------------------------
# position maps against the binary search they replaced
# ---------------------------------------------------------------------------
def _searchsorted_positions(level, ids):
    """The binary-search position map: clamped `searchsorted` into the
    sorted level, ok where the level holds the id."""
    pos = np.minimum(np.searchsorted(level, ids), len(level) - 1)
    return pos.astype(np.int32), level[pos] == ids


def _searchsorted_build(gdev, key, epoch_key, roots, fanouts, caps,
                        sampler):
    """Levels, blocks (hop order) and each hop's count of distinct ids, of
    one batch built with numpy's dedup and binary search over neighbors
    drawn with the build's keys."""
    N = gdev.num_nodes
    roots = np.asarray(roots)
    level = np.sort(np.where(roots >= 0, roots, N)).astype(np.int32)
    keys = jax.random.split(key, len(fanouts))
    ctx = mb.sampler_epoch_ctx(sampler, epoch_key, gdev)
    kw = {} if ctx is None else {"ranks": ctx}
    sample = jax.jit(lambda k, prev, r: sampler.sample(k, gdev, prev, r,
                                                       **kw),
                     static_argnums=2)
    levels, blocks, distinct = [level], [], []
    for h, (r, cap) in enumerate(zip(fanouts, caps)):
        prev = levels[-1]
        k_h = epoch_key if sampler.shared_randomness else keys[h]
        srcs, smask = (np.asarray(x) for x in sample(k_h, prev, r))
        uniq = np.unique(np.concatenate([prev, srcs.reshape(-1)]))
        distinct.append(len(uniq))
        uniq = uniq[:cap]
        nxt = np.full(cap, N, np.int32)
        nxt[:len(uniq)] = uniq
        self_pos, self_ok = _searchsorted_positions(nxt, prev)
        src_pos, src_ok = _searchsorted_positions(nxt, srcs.reshape(-1))
        blocks.append({
            "src_pos": src_pos.reshape(len(prev), r),
            "self_pos": self_pos,
            "edge_mask": smask & src_ok.reshape(len(prev), r) & (srcs < N),
            "dst_mask": (prev < N) & self_ok,
        })
        levels.append(nxt)
    return levels, blocks, distinct


def _batch_arrays(b):
    """A built batch's levels and blocks in the reference's layout."""
    blocks = [{f: np.asarray(getattr(blk, f)) for f in
               ("src_pos", "self_pos", "edge_mask", "dst_mask")}
              for blk in b.blocks[::-1]]
    return [np.asarray(lv) for lv in b.levels], blocks


def _digest(levels, blocks):
    h = hashlib.sha256()
    for a in levels + [blk[f] for blk in blocks for f in sorted(blk)]:
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("case", ["padded", "dropped"])
@pytest.mark.parametrize("sampler", ["biased", "uniform", "full", "labor"])
def test_positions_match_searchsorted(tiny_graph, gdev, sampler, case):
    """Each hop's positions and masks, taken from the dedup's sort, equal
    a clamped binary search into the new level bit for bit: with sentinel
    padding among the roots, and with caps below the distinct ids, so
    that ids are dropped."""
    s = sampling.resolve(sampler)
    roots = np.asarray(tiny_graph.train_ids[:256], np.int32)
    if case == "padded":
        roots[::3] = -1
        fanouts, caps = (5, 5), (1024, 1536)
    else:
        roots[-16:] = -1
        fanouts, caps = (5, 5), (320, 384)
    key, ekey = jax.random.key(3), jax.random.key(4)
    b = mb.build_batch(key, gdev, jnp.asarray(roots),
                       jnp.asarray(tiny_graph.labels), fanouts, caps, s,
                       epoch_key=ekey)
    want_levels, want_blocks, distinct = _searchsorted_build(
        gdev, key, ekey, roots, fanouts, caps, s)
    got_levels, got_blocks = _batch_arrays(b)
    for want, got in zip(want_levels, got_levels):
        assert want.dtype == got.dtype and np.array_equal(want, got)
    for h, (want, got) in enumerate(zip(want_blocks, got_blocks)):
        for f in want:
            assert want[f].dtype == got[f].dtype, (h, f)
            assert np.array_equal(want[f], got[f]), (h, f)
    # the cases hold what they name: sentinels among the roots, and
    # (only where asked) more distinct ids than the cap at every hop
    assert (got_levels[0] == tiny_graph.num_nodes).any()
    assert all((d > c) == (case == "dropped")
               for d, c in zip(distinct, caps))


def test_batch_hashes_match_searchsorted(tiny_graph):
    """12 batches across an epoch end, built through `build_batch` and the
    fused `DeviceBatchBuilder`, hash equal to the binary-search reference."""
    fanouts, caps = (5, 5), (512, 1024)
    st = BatchStream(tiny_graph, make_policy("comm_rand"), 128, fanouts,
                     caps, seed=11)
    bld = DeviceBatchBuilder.from_stream(st)
    last = bld.num_batches - 1
    cursors = [(0, p) for p in range(last - 5, last + 1)] + \
        [(1, p) for p in range(6)]
    labels = jnp.asarray(tiny_graph.labels)
    for epoch, pos in cursors:
        roots = st.root_batches(epoch)[pos]
        key, ekey = st.batch_key(epoch, pos), st.epoch_key(epoch)
        want = _digest(*_searchsorted_build(
            st.g, key, ekey, roots, fanouts, caps, st.sampler)[:2])
        direct = mb.build_batch(key, st.g, jnp.asarray(roots, jnp.int32),
                                labels, fanouts, caps, st.sampler,
                                epoch_key=ekey)
        assert _digest(*_batch_arrays(direct)) == want, (epoch, pos)
        assert _digest(*_batch_arrays(bld.build(epoch, pos))) == want, \
            (epoch, pos)
