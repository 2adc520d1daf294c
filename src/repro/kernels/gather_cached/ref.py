"""Pure-jnp oracle for the two-level cached gather.

`gather_cached_ref` is also the production `cache_impl="jnp"` path: XLA
lowers the double gather + select well enough on CPU/GPU, but it always
reads BOTH candidate rows (cache and global) per id — the Pallas kernel's
one row DMA per id, from the selected table, is what makes a hit skip the
global-matrix HBM read on TPU.
"""
import jax.numpy as jnp


def gather_cached_ref(cache, feats, pos, ids):
    """out[k] = cache[pos[ids[k]]] if pos[ids[k]] >= 0 else feats[ids[k]].

    cache: (C, F) float32 (exact copies of admitted rows); feats: (N, F);
    pos: (N,) int32 position map (-1 = miss); ids: (M,) int global row
    ids, entries outside [0, N) are padding and served from a clipped
    global row (callers mask them). Returns (M, F) float32.
    """
    N = feats.shape[0]
    gid = jnp.clip(ids.astype(jnp.int32), 0, N - 1)
    sel = pos[gid]
    hit = (sel >= 0) & (ids >= 0) & (ids < N)
    return jnp.where(
        hit[:, None],
        cache[jnp.maximum(sel, 0)].astype(jnp.float32),
        feats[gid].astype(jnp.float32))
