"""Plain mirror of the root order each epoch visits.

The policies' stated semantics: an epoch's order is a function of
`(seed, epoch)` alone. One draw of two uint32 words from numpy's
`default_rng((seed, epoch))` keys a murmur-style counter hash; RAND-ROOTS
sorts the training ids by the hash of their position, and COMM-RAND
shuffles whole communities by a block-level hash, merges consecutive runs
of `max(1, round(mix * n_blocks))` shuffled blocks into super-blocks, and
orders each super-block by an element-level hash of the position after
the block shuffle. Sorts are stable (ties keep block-concatenation order).
Written as loops over blocks, independently of the program's vectorized
code.
"""
from __future__ import annotations

import numpy as np

MIX_A = 0x85EBCA6B
MIX_B = 0xC2B2AE35
SALT_PERM = 0x9E3779B9
SALT_BLOCK = 0x7F4A7C15
SALT_ELEM = 0x94D049BB


def _hash(idx, words, salt: int) -> np.ndarray:
    x = np.asarray(idx).astype(np.uint32)
    for w in (np.uint32(words[0]) ^ np.uint32(salt), np.uint32(words[1])):
        x = x ^ w
        x = x * np.uint32(MIX_A)
        x = x ^ (x >> np.uint32(13))
        x = x * np.uint32(MIX_B)
        x = x ^ (x >> np.uint32(16))
    return x


def epoch_words(seed: int, epoch: int) -> np.ndarray:
    return np.random.default_rng((seed, epoch)).integers(
        0, 2 ** 32, size=2, dtype=np.uint32)


def epoch_order(traffic: dict, train_ids, communities, seed: int,
                epoch: int) -> np.ndarray:
    words = epoch_words(seed, epoch)
    train_ids = np.asarray(train_ids)
    if traffic["policy"] == "rand":
        keys = _hash(np.arange(len(train_ids)), words, SALT_PERM)
        return train_ids[np.argsort(keys, kind="stable")]
    if traffic["policy"] != "comm_rand":
        raise ValueError(f"no order mirror for policy {traffic['policy']!r}")
    comm = np.asarray(communities)[train_ids]
    blocks = [train_ids[comm == c] for c in np.unique(comm)]
    flat_start = np.cumsum([0] + [len(b) for b in blocks])
    border = np.argsort(_hash(np.arange(len(blocks)), words, SALT_BLOCK),
                        kind="stable")
    m = max(1, int(round(traffic["mix"] * len(blocks))))
    out, gpos = [], 0
    for s in range(0, len(blocks), m):
        ids, flat = [], []
        for b in border[s:s + m]:
            ids.append(blocks[b])
            flat.append(flat_start[b] + np.arange(len(blocks[b])))
        ids, flat = np.concatenate(ids), np.concatenate(flat)
        keys = _hash(gpos + np.arange(len(ids)), words, SALT_ELEM)
        out.append(ids[np.lexsort((flat, keys))])
        gpos += len(ids)
    return np.concatenate(out)


def root_batch(order: np.ndarray, pos: int, batch_size: int) -> np.ndarray:
    """Batch `pos` of an epoch order, -1-padded to `batch_size`."""
    out = np.full(batch_size, -1, np.int64)
    chunk = order[pos * batch_size:(pos + 1) * batch_size]
    out[:len(chunk)] = chunk
    return out
