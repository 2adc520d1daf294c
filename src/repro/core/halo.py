"""Distributed feature access with a *static halo budget* (DESIGN.md §2).

The feature table is sharded into community-contiguous node ranges (one per
device on the `shard` mesh axis). A batch gather splits into:

  local     — rows this device owns (HBM gather only)
  halo      — rows owned by the ±`halo` neighboring shards: fixed-size
              (r_cap) request/response exchanges over collective_permute
  global    — fallback: all-gather every request id, every shard serves its
              rows, psum_scatter returns them (what a structure-agnostic
              policy requires)

COMM-RAND's community-aligned batches keep nearly all accesses in
local+halo, so the collective roofline term scales with `2*halo*r_cap*F`
instead of `D*K*F` — the pod-scale analogue of the paper's cache story.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def owner_of(ids, n_per_shard):
    return ids // n_per_shard


def halo_gather(feats_local, ids, *, n_per_shard: int, r_cap: int,
                halo: int, axis: str = "shard"):
    """Inside shard_map. feats_local: (Ns, F); ids: (K,) global node ids
    (sentinel >= N allowed -> zero rows). Returns ((K, F), dropped_count).

    Remote ids beyond ±halo shards are DROPPED (zero rows) and counted —
    calibration must pick (halo, r_cap) so this is negligible for the
    policy in use.
    """
    D = lax.axis_size(axis)
    me = lax.axis_index(axis)
    K = ids.shape[0]
    F = feats_local.shape[1]
    n_total = n_per_shard * D
    valid = ids < n_total
    owner = jnp.where(valid, owner_of(ids, n_per_shard), D)

    out = jnp.zeros((K, F), feats_local.dtype)
    loc = owner == me
    lidx = jnp.where(loc, ids - me * n_per_shard, 0)
    out = out + jnp.where(loc[:, None], feats_local[lidx], 0)
    served = loc

    for h in range(1, halo + 1):
        # at h == D/2 both directions reach the SAME shard — visiting it
        # twice would serve (and double) every row it owns
        for sign in ((1,) if (2 * h) % D == 0 else (1, -1)):
            tgt = (me + sign * h) % D
            want = owner == tgt
            # up to r_cap request slots for this neighbor
            pos = jnp.argsort(~want)[:r_cap]
            pvalid = want[pos]
            req = jnp.where(pvalid, ids[pos] - tgt * n_per_shard, 0)
            fwd = [(i, (i + sign * h) % D) for i in range(D)]
            rev = [(i, (i - sign * h) % D) for i in range(D)]
            got_req = lax.ppermute(req, axis, perm=fwd)
            got_val = lax.ppermute(pvalid, axis, perm=fwd)
            rows = feats_local[jnp.clip(got_req, 0, feats_local.shape[0] - 1)]
            rows = rows * got_val[:, None].astype(rows.dtype)
            back = lax.ppermute(rows, axis, perm=rev)
            out = out.at[pos].add(
                jnp.where(pvalid[:, None], back, 0))
            served = served | (want & jnp.zeros_like(want).at[pos].set(
                pvalid, mode="drop"))

    dropped = jnp.sum(valid & ~served)
    return out, dropped


def global_gather(feats_local, ids, *, n_per_shard: int,
                  axis: str = "shard", chunk: int = 32768):
    """All-to-all fallback: every shard serves every device's requests.
    Collective bytes ~ D * K * F — the structure-agnostic cost. Requests are
    served in `chunk`-sized waves to bound the (D, chunk, F) exchange
    buffer."""
    D = lax.axis_size(axis)
    me = lax.axis_index(axis)
    n_total = n_per_shard * D
    K = ids.shape[0]
    chunk = min(chunk, K)
    n_chunks = (K + chunk - 1) // chunk
    pad = n_chunks * chunk - K
    ids = jnp.pad(ids, (0, pad), constant_values=n_total)

    def serve(ids_c):
        all_ids = lax.all_gather(ids_c, axis)            # (D, Kc)
        all_owner = jnp.where(all_ids < n_total,
                              owner_of(all_ids, n_per_shard), D)
        mine = all_owner == me
        lidx = jnp.where(mine, all_ids - me * n_per_shard, 0)
        contrib = feats_local[lidx] * mine[..., None].astype(
            feats_local.dtype)
        return lax.psum_scatter(contrib, axis, scatter_dimension=0)

    out = lax.map(serve, ids.reshape(n_chunks, chunk))
    out = out.reshape(n_chunks * chunk, -1)[:K]
    return out, jnp.zeros((), jnp.int32)


def gather_for_policy(feats_local, ids, *, n_per_shard, r_cap, halo,
                      axis="shard", mode="halo"):
    if mode == "halo":
        return halo_gather(feats_local, ids, n_per_shard=n_per_shard,
                           r_cap=r_cap, halo=halo, axis=axis)
    return global_gather(feats_local, ids, n_per_shard=n_per_shard,
                         axis=axis)


def halo_gather_np(feats_shards, ids_shards, *, n_per_shard: int,
                   r_cap: int, halo: int):
    """Host-side mirror of `halo_gather` simulating ALL D shards at once.

    feats_shards: (D, Ns, F); ids_shards: (D, K) global node ids (sentinel
    >= Ns*D -> zero rows). Returns ((D, K, F) rows, (D,) dropped counts),
    step-for-step identical to the on-device exchange — including the
    stable argsort request packing and the r_cap truncation — so property
    tests can sweep random graphs without spawning a device mesh, and one
    subprocess test pins this mirror `==` the `shard_map` path.
    """
    import numpy as np

    feats = np.asarray(feats_shards)
    ids = np.asarray(ids_shards)
    D, Ns, F = feats.shape
    K = ids.shape[1]
    n_total = Ns * D
    valid = ids < n_total
    owner = np.where(valid, ids // n_per_shard, D)

    out = np.zeros((D, K, F), feats.dtype)
    served = np.zeros((D, K), bool)
    for me in range(D):
        loc = owner[me] == me
        lidx = np.where(loc, ids[me] - me * Ns, 0)
        out[me] += np.where(loc[:, None], feats[me][lidx], 0)
        served[me] = loc

    for h in range(1, halo + 1):
        # mirror of the device loop's h == D/2 dedup
        for sign in ((1,) if (2 * h) % D == 0 else (1, -1)):
            # every device's request packet for its (me + sign*h) neighbor
            reqs = np.zeros((D, r_cap), np.int64)
            pvalids = np.zeros((D, r_cap), bool)
            poss = np.zeros((D, r_cap), np.int64)
            for me in range(D):
                tgt = (me + sign * h) % D
                want = owner[me] == tgt
                pos = np.argsort(~want, kind="stable")[:r_cap]
                pvalid = want[pos]
                reqs[me] = np.where(pvalid, ids[me][pos] - tgt * Ns, 0)
                pvalids[me] = pvalid
                poss[me] = pos
            for me in range(D):
                # ppermute fwd delivers device src's packet to
                # (src + sign*h) % D — i.e. `me` receives from src below
                src = (me - sign * h) % D
                got_req, got_val = reqs[src], pvalids[src]
                rows = feats[me][np.clip(got_req, 0, Ns - 1)]
                rows = rows * got_val[:, None].astype(rows.dtype)
                # rev returns the served rows to src
                back, pvalid, pos = rows, pvalids[src], poss[src]
                np.add.at(out[src], pos,
                          np.where(pvalid[:, None], back, 0))
                served[src][pos[pvalid]] = True

    dropped = np.sum(valid & ~served, axis=1)
    return out, dropped


def collective_bytes_model(K: int, F: int, D: int, r_cap: int, halo: int,
                           mode: str, itemsize: int = 4) -> int:
    """Napkin model used by the §Roofline analysis and tests."""
    if mode == "halo":
        return 2 * halo * r_cap * (F * itemsize + 8)
    return D * K * 4 + K * F * itemsize * 2     # ids all-gather + psum_scatter
