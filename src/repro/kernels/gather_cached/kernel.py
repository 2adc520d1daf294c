"""Pallas TPU kernel: two-level (cache-or-global) feature row gather.

Layer-0 of the GNN reads one (F,)-row per unique input node. With a
device-resident cache (`repro.featcache.CachePlan`) each row lives either
in the compact (C, F) cache array or in the global (N, F) feature matrix:

    out[k] = cache[pos[ids[k]]]   if pos[ids[k]] >= 0   (hit)
           = feats[ids[k]]        otherwise             (miss)

This is the tiled row gather of `repro.kernels.gather_agg` with fanout 1
and two tables: each id is encoded as its global row (miss) or as
-(cache row) - 1 (hit), and the kernel issues exactly one row DMA per id
from the table the sign selects. That is the cache's bandwidth story: a hit
never touches the global matrix. Output rows stay in id order.

Backward needs no new kernel: d_cache/d_feats are masked scatter-adds of
the cotangent rows, exactly `gather_agg_bwd_dx_pallas` with fanout 1 (see
`ops.py`).
"""
from __future__ import annotations

from repro.kernels.gather_agg.kernel import gather_tiles


def gather_cached_fwd_pallas(cache, feats, code, *, interpret: bool = False):
    """cache: (C, F); feats: (N, F) of the same dtype; code: (M,) int32,
    a global row v >= 0 (miss) or -(cache row) - 1 (hit). Returns (M, F)
    float32."""
    f = feats.shape[1]

    def copy_rows(rows, _, o_ref):
        o_ref[...] = rows[0][:, :f]

    return gather_tiles((feats, cache), code.reshape(-1, 1), None,
                        copy_rows, f, name="gather_cached_fwd",
                        interpret=interpret)
