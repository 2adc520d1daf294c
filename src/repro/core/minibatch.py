"""Static-shape mini-batch construction (the TPU-native core of COMM-RAND).

A batch is a tower of node levels F_0 (roots) ⊂ F_1 ⊂ ... ⊂ F_L (input
level), built by pluggable neighbor sampling (`repro.sampling`) + *static-
size dedup* (`_dedup`: one sort, the first `cap` distinct ids). Each hop's
position maps come from that same sort (`_sorted_positions`: an id's rank
among the distinct values, carried back through the sort's permutation),
with no search into the new level. The caps are CALIBRATED PER
(POLICY, SAMPLER) (`calibrate_caps`): community-biased policies — and
LABOR's shared-randomness sampler — dedup far more aggressively, so their
compiled batches carry smaller gather buffers: the paper's working-set
reduction, expressed at compile time (DESIGN.md §2).

The sampler rides through jit as a STATIC argument (samplers are frozen
dataclasses), so each sampler gets its own compiled builder. Samplers with
`shared_randomness` (LABOR) receive the EPOCH-level key — identical across
hops and batches — instead of the per-(batch, hop) key, which is what
makes overlapping neighborhoods pick identical neighbors.

Blocks are stored input-side first: blocks[0] maps F_L -> F_{L-1}. Every dst
has exactly `fanout` sampled source slots + one self slot, so aggregation is
a masked mean over a dense (n_dst, fanout, dim) gather — no segment ops.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import sampling
from repro.core import partition
from repro.graphs.csr import DeviceGraph, Graph


@jax.tree_util.register_dataclass
@dataclass
class Block:
    src_pos: jnp.ndarray     # (n_dst, fanout) int32 positions into src level
    self_pos: jnp.ndarray    # (n_dst,) int32 position of dst in src level
    edge_mask: jnp.ndarray   # (n_dst, fanout) bool
    dst_mask: jnp.ndarray    # (n_dst,) bool


@jax.tree_util.register_dataclass
@dataclass
class MiniBatch:
    levels: List[jnp.ndarray]  # per-level sorted unique node ids, 0=roots
    node_mask: jnp.ndarray   # (cap_L,) bool — input level validity
    blocks: List[Block]      # input-side first
    labels: jnp.ndarray      # (B,) int32 (aligned with levels[0])
    label_mask: jnp.ndarray  # (B,) bool

    @property
    def node_ids(self):
        """Input-level unique node ids (feature-gather index)."""
        return self.levels[-1]

    @property
    def roots(self):
        return self.levels[0]

    @property
    def num_unique(self):
        return self.node_mask.sum()


def _positions(level: jnp.ndarray, ids: jnp.ndarray):
    """Map node ids -> positions in the sorted unique `level` array by
    binary search (the labels' root map; the hops use `_sorted_positions`)."""
    pos = jnp.searchsorted(level, ids).astype(jnp.int32)
    pos = jnp.minimum(pos, level.shape[0] - 1)
    ok = level[pos] == ids
    return pos, ok


def _dedup(ids: jnp.ndarray, cap: int, fill: int):
    """Static-size dedup: the `cap` smallest distinct values of `ids` in
    ascending order, padded with `fill` (bit-identical to
    `jnp.unique(ids, size=cap, fill_value=fill)`), plus what the position
    maps reuse of its one sort: the new-value marks in sorted order and
    the sort's permutation."""
    n = ids.shape[0]
    sorted_ids, perm = lax.sort((ids, lax.iota(jnp.int32, n)), num_keys=1)
    new = jnp.concatenate([jnp.ones((1,), bool),
                           sorted_ids[1:] != sorted_ids[:-1]])
    first = jnp.nonzero(new, size=cap)[0]
    level = jnp.where(jnp.arange(cap) < new.sum(), sorted_ids[first], fill)
    return level, new, perm


def _sorted_positions(new: jnp.ndarray, perm: jnp.ndarray, cap: int):
    """Positions of the ids `_dedup` sorted in the level it made: each
    id's rank among the distinct values (running count of the new-value
    marks), carried back to its slot by sorting the ranks on the sort's
    permutation (on a v5e this un-permute beats a scatter through `perm`,
    1.6 against 3.9 ms at 485,760 ids). An id ranked past the cap gets
    `cap - 1` and ok False, as a clamped binary search into the level
    would."""
    rank = jnp.cumsum(new, dtype=jnp.int32) - 1
    _, pos = lax.sort((perm, rank), num_keys=1)
    return jnp.minimum(pos, cap - 1), pos < cap


def sampler_epoch_ctx(sampler, epoch_key, g: DeviceGraph):
    """Per-epoch device state a shared-randomness sampler can precompute
    once (LABOR's node ranks). None for samplers without one. The batch
    builder computes it once per build; `repro.pipeline.DeviceBatchBuilder`
    hoists it further, to once per EPOCH."""
    fn = getattr(sampler, "epoch_ctx", None)
    if sampler.shared_randomness and callable(fn):
        return fn(epoch_key, g)
    return None


def _build_batch_impl(key, epoch_key, g: DeviceGraph, roots, labels_all,
                      fanouts: Tuple[int], caps: Tuple[int],
                      sampler, shared_ctx=None) -> MiniBatch:
    """The (jit-traceable) build body, shared by the host-driven
    `_build_batch` below and the fused on-device builder in
    `repro.pipeline.builder` — ONE implementation so the async pipeline's
    batch sequence is bit-exact against the synchronous stream."""
    N = g.num_nodes
    B = roots.shape[0]
    # every device op of the build runs under one of the named scopes
    # below (op metadata only: they add no op and change no value), so a
    # device trace splits the build into roots, each hop's sampling,
    # dedup and position maps, and labels
    with jax.named_scope("build/roots"):
        root_mask = roots >= 0
        level = jnp.where(root_mask, roots, N).astype(jnp.int32)
        # level 0 is sorted like every level (the labels' binary search
        # relies on it)
        level = jnp.sort(level)

    levels = [level]
    blocks = []
    for h, (r, cap) in enumerate(zip(fanouts, caps)):
        prev = levels[-1]
        with jax.named_scope(f"build/hop{h}/sample"):
            if h == 0:
                keys = jax.random.split(key, len(fanouts))
                # shared per-epoch sampler state (LABOR ranks): computed
                # once per build instead of once per hop — a pure
                # function of the epoch key, so hoisting cannot change
                # any pick
                if shared_ctx is None:
                    shared_ctx = sampler_epoch_ctx(sampler, epoch_key, g)
            # shared-randomness samplers (LABOR) draw from the epoch key
            # so the same source node picks the same neighbors at every
            # hop and batch
            k_h = epoch_key if sampler.shared_randomness else keys[h]
            if shared_ctx is not None:
                srcs, smask = sampler.sample(k_h, g, prev, r,
                                             ranks=shared_ctx)
            else:
                srcs, smask = sampler.sample(k_h, g, prev, r)
        with jax.named_scope(f"build/hop{h}/dedup"):
            all_ids = jnp.concatenate([prev, srcs.reshape(-1)])
            nxt, new, perm = _dedup(all_ids, cap, N)
        with jax.named_scope(f"build/hop{h}/positions"):
            pos, ok = _sorted_positions(new, perm, cap)
            n_dst = prev.shape[0]
            blocks.append(Block(
                src_pos=pos[n_dst:].reshape(n_dst, r),
                self_pos=pos[:n_dst],
                edge_mask=(smask & ok[n_dst:].reshape(n_dst, r)
                           & (srcs < N)),
                dst_mask=(prev < N) & ok[:n_dst],
            ))
        levels.append(nxt)

    top = levels[-1]
    with jax.named_scope("build/labels"):
        # labels aligned to the SORTED root level: re-gather via positions
        labels = jnp.where(root_mask, labels_all[jnp.where(
            root_mask, roots, 0)], 0)
        root_pos, _ = _positions(levels[0], jnp.where(root_mask, roots, N))
        lab_sorted = jnp.zeros((B,), labels_all.dtype).at[root_pos].set(
            jnp.where(root_mask, labels, 0), mode="drop")
        lmask = jnp.zeros((B,), bool).at[root_pos].set(root_mask,
                                                       mode="drop")
        node_mask = top < N
        label_mask = lmask & (levels[0] < N)
    return MiniBatch(
        levels=levels,
        node_mask=node_mask,
        blocks=blocks[::-1],
        labels=lab_sorted,
        label_mask=label_mask,
    )


@functools.partial(jax.jit,
                   static_argnames=("fanouts", "caps", "sampler"))
def _build_batch(key, epoch_key, g: DeviceGraph, roots, labels_all,
                 fanouts: Tuple[int], caps: Tuple[int],
                 sampler, shared_ctx=None) -> MiniBatch:
    return _build_batch_impl(key, epoch_key, g, roots, labels_all,
                             fanouts, caps, sampler, shared_ctx)


def build_batch(key, g: DeviceGraph, roots, labels_all, fanouts: Tuple[int],
                caps: Tuple[int], sampler=0.5, mode: str = "sample", *,
                epoch_key=None) -> MiniBatch:
    """roots: (B,) int32 with -1 padding. caps: per-level unique caps,
    len == len(fanouts), cap for levels 1..L (level 0 cap is B).

    `sampler` is a `repro.sampling` sampler (or registry name/spec); a
    bare float is the legacy signature and selects the biased two-phase
    draw at that `p` (`mode="all"` likewise maps to the full-neighborhood
    sampler) — see `sampling.resolve` for the one precedence rule.
    `epoch_key` feeds shared-randomness samplers; it defaults to `key`,
    which keeps direct calls deterministic but shares picks only within
    this one batch — streams pass the real epoch key.
    """
    s = sampling.resolve(sampler, mode)
    if epoch_key is None:
        epoch_key = key
    return _build_batch(key, epoch_key, g, roots, labels_all,
                        tuple(fanouts), tuple(caps), s)


# ---------------------------------------------------------------------------
# numpy reference builder (exact dedup; calibration + test oracle)
# ---------------------------------------------------------------------------
def build_batch_np(rng: np.random.Generator, graph: Graph, roots, fanouts,
                   sampler=0.5, ctx: dict = None):
    """Returns per-level unique-node counts + the input-level footprint.
    `sampler` follows `build_batch`'s convention (float p == biased);
    `ctx` carries per-epoch shared sampler state (LABOR's ranks) across
    batches of one epoch."""
    s = sampling.resolve(sampler)
    ctx = {} if ctx is None else ctx
    level = np.unique(roots[roots >= 0])
    sizes = [len(level)]
    for r in fanouts:
        srcs = s.sample_level_np(rng, graph, level, r, ctx)
        level = np.unique(np.concatenate([level] + list(srcs)))
        sizes.append(len(level))
    return sizes, level


def calibrate_caps(graph: Graph, policy, batch_size: int,
                   fanouts, n_probe: int = 6, margin: float = 1.15,
                   seed: int = 0, align: int = 128) -> Tuple[int, ...]:
    """Policy-derived static caps: max unique nodes per level over probe
    batches x margin, rounded up to `align` (TPU-friendly shapes). The
    probe samples through the policy's BOUND SAMPLER (`sampler_spec()`),
    so e.g. LABOR's collapsed footprint yields smaller caps.

    Probe batch indices are drawn uniformly across the epoch: under
    comm_rand the LEADING batches of an epoch order are community-pure and
    under-estimate the footprint of the late, mixed batches."""
    # salt 0 = legacy stream slot (trailing-zero tuples are
    # stream-identical by the SeedSequence spec): calibrated caps
    # stay bit-stable against pre-conversion runs
    rng = np.random.default_rng((seed, 0))
    s = sampling.for_policy(policy)
    maxes = np.zeros(len(fanouts), np.int64)
    probes = 0
    while probes < n_probe:
        ctx = {}                        # fresh shared state per probe epoch
        batches = partition.batches_for_epoch(
            graph.train_ids, graph.communities, policy, batch_size, rng)
        take = min(max(1, n_probe - probes), len(batches))
        idx = np.sort(rng.choice(len(batches), size=take, replace=False))
        for b in batches[idx]:
            sizes, _ = build_batch_np(rng, graph, b, fanouts, s, ctx=ctx)
            maxes = np.maximum(maxes, sizes[1:])
            probes += 1
            if probes >= n_probe:
                break
    caps = []
    lo = batch_size
    for m in maxes:
        c = int(np.ceil(m * margin / align) * align)
        c = max(c, lo + align)       # level must fit its predecessor
        caps.append(c)
        lo = c
    return tuple(caps)


def feature_bytes(batch_or_cap, feat_dim: int, itemsize: int = 4) -> int:
    """Paper Fig 6 metric: input feature bytes gathered per batch."""
    if isinstance(batch_or_cap, (int, np.integer)):
        return int(batch_or_cap) * feat_dim * itemsize
    return int(batch_or_cap.num_unique) * feat_dim * itemsize
