"""Plain mirror of the batch build: biased two-phase sampling and dedup.

Semantics mirrored (numpy, on the host):

- Level 0 is the batch's roots, sorted, padded with the sentinel N.
- Hop h draws `fanout` neighbors for every row of level h with the key
  `split(batch_key, L)[h]`, split again into (class, offset, unused)
  keys; `u_class` and `u_off` are `jax.random.uniform` of shape
  (rows, fanout). A row takes an intra-community neighbor when
  `u_class < p*n_i / (p*n_i + (1-p)*n_o)` (1 when it has no inter
  neighbor, 0 when it has no intra one), then the neighbor at offset
  `floor(u_off * n)` of that class in its intra-first adjacency row.
  Isolated rows sample themselves; padded rows sample the sentinel.
- Level h+1 is the sorted unique union of level h and its samples,
  padded with the sentinel to the level's cap, and never cut to it: the
  caps are the program's own (calibrated on a few probe batches), so a
  level that outgrows its cap is the program's fault, not the semantics.
  `check` counts every id a cap left out and every edge it masked off.

The threshold is computed exactly here (float64). A draw whose uniform
lies within `AMBIGUOUS` of it is decided by rounding in the program's
float32 division, not by the semantics: either class's pick is accepted
there, and the reference batch takes the one the program took. (One
pick decides whether a node joins the next level, and so the row, and
the uniforms, of every later node of that level: left to its own
rounding, the reference would sample a different batch.)
"""
from __future__ import annotations

import jax
import numpy as np

AMBIGUOUS = 1e-6


def uniforms(key, rows: int, fanout: int):
    k1, k2, _ = jax.random.split(key, 3)
    return (np.asarray(jax.random.uniform(k1, (rows, fanout))),
            np.asarray(jax.random.uniform(k2, (rows, fanout))))


def sample(graph, prev, u_class, u_off, p: float):
    """(srcs, smask, alt): picks per slot, the slot mask, and the other
    class's pick where the class draw is ambiguous (-1 elsewhere)."""
    N = graph.num_nodes
    valid = prev < N
    safe = np.where(valid, prev, 0)
    start = graph.indptr[safe]
    deg = graph.indptr[safe + 1] - start
    ni = graph.n_intra[safe].astype(np.int64)
    no = deg - ni
    with np.errstate(invalid="ignore", divide="ignore"):
        pe = np.where(no == 0, 1.0, np.where(
            ni == 0, 0.0, p * ni / (p * ni + (1.0 - p) * no)))
    pe = pe[:, None]
    intra = u_class < pe
    u32 = u_off.astype(np.float32)
    off_i = np.floor(u32 * ni[:, None].astype(np.float32)).astype(np.int64)
    off_o = ni[:, None] + np.floor(
        u32 * no[:, None].astype(np.float32)).astype(np.int64)
    hi = np.maximum(deg - 1, 0)[:, None]

    def pick(off):
        off = np.clip(off, 0, hi)
        e = np.minimum(start[:, None] + off, len(graph.indices) - 1)
        s = graph.indices[e].astype(np.int64)
        s = np.where(deg[:, None] > 0, s, safe[:, None])
        return np.where(valid[:, None], s, N)

    pi, po = pick(off_i), pick(off_o)
    srcs = np.where(intra, pi, po)
    amb = (np.abs(u_class - pe) < AMBIGUOUS) & (ni > 0)[:, None] & \
        (no > 0)[:, None]
    alt = np.where(amb, np.where(intra, po, pi), -1)
    smask = np.broadcast_to((valid & (deg > 0))[:, None], srcs.shape)
    return srcs, smask, alt


def next_level(prev, srcs, cap: int, N: int):
    """Sorted unique real ids of `prev` and `srcs`, sentinel-padded to
    `cap`; longer than `cap` where they do not fit."""
    nxt = np.unique(np.concatenate([prev, srcs.ravel()]))
    nxt = nxt[nxt < N]
    return np.concatenate([nxt, np.full(max(cap - len(nxt), 0), N,
                                        np.int64)])


def positions(level, ids):
    pos = np.minimum(np.searchsorted(level, ids), len(level) - 1)
    return pos, level[pos] == ids


def sorted_roots(graph, roots):
    N = graph.num_nodes
    level = np.sort(np.where(roots >= 0, roots, N)).astype(np.int64)
    real = level < N
    labels = np.where(real, graph.labels[np.minimum(level, N - 1)], 0)
    return level, labels.astype(np.int32), real


def _follow(srcs, alt, got_hop, got_next):
    """Where the class draw is ambiguous and the program kept the other
    class's pick, take it."""
    if got_hop is None:
        return srcs, 0
    prog = got_next[np.clip(got_hop["src_pos"], 0, len(got_next) - 1)]
    take = (alt >= 0) & got_hop["edge_mask"] & (prog == alt)
    return np.where(take, alt, srcs), int(take.sum())


def build(graph, roots, key, fanouts, caps, p: float,
          follow: dict = None) -> dict:
    """The reference batch, hop order: `hops[h]` maps level h (dst) to
    level h+1 (src). `follow` (the program's batch, same layout) only
    settles ambiguous draws; `alt_taken` counts them."""
    N = graph.num_nodes
    level, labels, lmask = sorted_roots(graph, roots)
    levels, hops, alt_taken = [level], [], 0
    keys = jax.random.split(key, len(fanouts))
    for h, (r, cap) in enumerate(zip(fanouts, caps)):
        prev = levels[-1]
        srcs, smask, alt = sample(graph, prev,
                                  *uniforms(keys[h], len(prev), r), p)
        if follow is not None and np.array_equal(follow["levels"][h], prev):
            srcs, n = _follow(srcs, alt, follow["hops"][h],
                              follow["levels"][h + 1])
            alt_taken += n
        nxt = next_level(prev, srcs, cap, N)
        self_pos, self_ok = positions(nxt, prev)
        src_pos, src_ok = positions(nxt, srcs.ravel())
        src_ok = src_ok.reshape(srcs.shape)
        hops.append({"src_pos": src_pos.reshape(srcs.shape),
                     "self_pos": self_pos,
                     "edge_mask": smask & src_ok & (srcs < N),
                     "dst_mask": (prev < N) & self_ok})
        levels.append(nxt)
    return {"levels": levels, "hops": hops, "labels": labels,
            "label_mask": lmask, "alt_taken": alt_taken}


def check(graph, roots, key, fanouts, caps, p: float, got: dict) -> int:
    """Violations of the mirrored semantics in the program's batch `got`
    (same layout as `build`'s). Each hop is checked on the program's own
    dst level, which the previous hop's check has vouched for."""
    N = graph.num_nodes
    bad = 0
    level, labels, lmask = sorted_roots(graph, roots)
    bad += int(np.sum(got["levels"][0] != level))
    bad += int(np.sum(got["label_mask"] != lmask))
    bad += int(np.sum((got["labels"] != labels) & lmask))
    keys = jax.random.split(key, len(fanouts))
    for h, (r, cap) in enumerate(zip(fanouts, caps)):
        prev, nxt, b = got["levels"][h], got["levels"][h + 1], got["hops"][h]
        srcs, smask, alt = sample(graph, prev,
                                  *uniforms(keys[h], len(prev), r), p)
        # ambiguous slots may hold the other class's pick instead
        srcs, _ = _follow(srcs, alt, b, nxt)
        prog = nxt[np.clip(b["src_pos"], 0, len(nxt) - 1)]
        want = next_level(prev, srcs, cap, N)
        bad += max(len(want) - len(nxt), 0)     # ids the cap left out
        bad += int(np.sum(nxt != want[:len(nxt)]))
        # every real sample and every real dst row is in the level: an
        # edge or a row masked off for want of room is a violation
        want_mask = smask & (srcs < N)
        bad += int(np.sum(b["edge_mask"] != want_mask))
        bad += int(np.sum(b["edge_mask"] & (prog != srcs)))
        self_pos, self_ok = positions(nxt, prev)
        dst_mask = prev < N
        bad += int(np.sum(b["dst_mask"] != dst_mask))
        bad += int(np.sum(dst_mask & self_ok & (b["self_pos"] != self_pos)))
    return bad
