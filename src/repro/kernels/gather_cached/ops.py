"""Public two-level cached gather with a custom VJP + device hit counters.

`gather_cached(cache, feats, pos, ids)` serves feature row `ids[k]` from
the cache array when `pos[ids[k]] >= 0` and from the global matrix
otherwise, returning `(rows, hits, misses)` — the counters are computed on
device (`cache_stats`, bit-matched by the numpy mirror
`repro.featcache.plan.cache_stats_np`) so measured hit rates cost no extra
host sync beyond the metrics the trainer already pulls.

`impl="auto"` follows the same rule as `gather_agg`: Pallas on TPU, the
jnp reference elsewhere (interpret mode is a simulator — correct, but for
validation, never CPU throughput). The backward reuses
`gather_agg_bwd_dx_pallas` twice (fanout-1 masked scatter-adds of the
cotangent into cache rows for hits and global rows for misses).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gather_agg.kernel import gather_agg_bwd_dx_pallas
from repro.kernels.gather_cached.kernel import gather_cached_fwd_pallas
from repro.kernels.gather_cached.ref import gather_cached_ref

CACHE_IMPLS = ("auto", "jnp", "pallas")


def resolve_cache_impl(impl: str) -> str:
    """'auto' -> 'pallas' on TPU backends, 'jnp' elsewhere."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(
            f"cache impl must be one of {CACHE_IMPLS}, got {impl!r}")
    return impl


def _hit_mask(pos, ids, num_nodes: int):
    gid = jnp.clip(ids, 0, num_nodes - 1)
    sel = pos[gid]
    hit = (sel >= 0) & (ids >= 0) & (ids < num_nodes)
    return gid, sel, hit


def cache_stats(pos, ids, num_nodes: int):
    """Device-side (hits, misses) int32 counters over the VALID entries of
    `ids` (entries outside [0, num_nodes) are padding and count as
    neither). Mirror: `repro.featcache.plan.cache_stats_np`."""
    ids = ids.astype(jnp.int32)
    _, _, hit = _hit_mask(pos, ids, num_nodes)
    valid = (ids >= 0) & (ids < num_nodes)
    hits = jnp.sum(hit, dtype=jnp.int32)
    return hits, jnp.sum(valid, dtype=jnp.int32) - hits


def cache_ref_updates(pos, ids, capacity: int):
    """Per-SLOT hit counts and per-NODE miss counts for one batch of reads
    — the extended device counters behind the dynamic CLOCK admission loop
    (`repro.featcache.dynamic`).

    Returns `(slot_hits (C,) int32, node_miss (N,) int32)` over the VALID
    entries of `ids` (same validity rule as `cache_stats`; their sums equal
    its scalar hits/misses). `slot_hits > 0` is the per-slot reference bit;
    `node_miss` feeds the candidate-frequency accumulator the epoch refill
    admits from. Mirror: `repro.featcache.plan.cache_ref_updates_np`."""
    num_nodes = pos.shape[0]
    ids = ids.astype(jnp.int32)
    gid, sel, hit = _hit_mask(pos, ids, num_nodes)
    valid = (ids >= 0) & (ids < num_nodes)
    slot_hits = jnp.zeros((capacity,), jnp.int32).at[
        jnp.where(hit, sel, capacity)].add(1, mode="drop")
    node_miss = jnp.zeros((num_nodes,), jnp.int32).at[
        jnp.where(valid & ~hit, gid, num_nodes)].add(1, mode="drop")
    return slot_hits, node_miss


def _fwd_pallas(cache, feats, pos, ids, interpret):
    gid, sel, hit = _hit_mask(pos, ids, feats.shape[0])
    code = jnp.where(hit, -1 - sel, gid).astype(jnp.int32)
    return gather_cached_fwd_pallas(cache, feats, code, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gather_cached(cache, feats, pos, ids, interpret):
    return _fwd_pallas(cache, feats, pos, ids, interpret)


def _gather_cached_fwd(cache, feats, pos, ids, interpret):
    out = _fwd_pallas(cache, feats, pos, ids, interpret)
    return out, (cache, feats, pos, ids)


def _gather_cached_bwd(interpret, res, g):
    cache, feats, pos, ids = res
    M = ids.shape[0]
    gid, sel, hit = _hit_mask(pos, ids, feats.shape[0])
    d_cache = gather_agg_bwd_dx_pallas(
        jnp.maximum(sel, 0).reshape(M, 1),
        hit.astype(jnp.float32).reshape(M, 1), g, cache.shape[0],
        interpret=interpret)
    d_feats = gather_agg_bwd_dx_pallas(
        gid.reshape(M, 1),
        (~hit).astype(jnp.float32).reshape(M, 1), g, feats.shape[0],
        interpret=interpret)
    return (d_cache.astype(cache.dtype), d_feats.astype(feats.dtype),
            np.zeros(pos.shape, jax.dtypes.float0),
            np.zeros(ids.shape, jax.dtypes.float0))


_gather_cached.defvjp(_gather_cached_fwd, _gather_cached_bwd)


def gather_cached(cache, feats, pos, ids, *, impl: str = "auto"):
    """Two-level gather: `(rows (M, F) float32, hits, misses)`.

    cache: (C, F) admitted rows (exact copies, so hits are bit-identical
    to global reads); feats: (N, F); pos: (N,) int32 (-1 = miss); ids:
    (M,) int global row ids — entries outside [0, N) are padding, served
    from a clipped global row (mask downstream) and excluded from the
    counters. Differentiable in cache and feats; call inside jit (the
    trainer's step functions already are). The counters are pure jnp
    reductions: a caller that discards them (`apply_gnn` does) pays
    nothing under jit (XLA dead-code-eliminates the unused subgraph), and
    `cache_stats` is the ONE counting rule — the trainer's per-batch
    metering calls the same function.
    """
    impl = resolve_cache_impl(impl)
    ids = ids.astype(jnp.int32)
    hits, misses = cache_stats(pos, ids, feats.shape[0])
    if impl == "jnp":
        return gather_cached_ref(cache, feats, pos, ids), hits, misses
    interpret = jax.default_backend() != "tpu"
    return (_gather_cached(cache, feats, pos, ids, interpret),
            hits, misses)
