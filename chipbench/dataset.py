"""A configuration's graph: generated, prepared by the program, cached.

Two caches under `chipbench/.cache/graphs/`, both kept only within a
checkout:

- the generated graph, keyed by its spec;
- the graph after the program's one-time preprocessing
  (`repro.core.reorder.prepare`: community relabeling and intra-first
  adjacency rows), keyed by the spec and a hash of every file under
  `src/repro`, so a change to the program never reads a stale file.

When the prepared graph is made, it is checked against the generated one:
it must be the same graph under a relabeling (the relabeling is read back
from node tags carried through `prepare` as a feature column), with the
same labels, communities and splits, and each adjacency row must list
its intra-community neighbors first, `n_intra` of them. So the reference
can use the prepared graph as data it made itself.
"""
from __future__ import annotations

import hashlib
import os
import sys
import time
import zipfile
from pathlib import Path

import numpy as np

from chipbench import graphgen

_RAW = ("indptr", "indices", "labels", "communities", "train_ids",
        "val_ids", "test_ids")
_PREPARED = _RAW + ("n_intra",)


def _log(msg: str, t0: float) -> None:
    print(f"chipbench: {msg} in {time.perf_counter() - t0:.3f}s",
          file=sys.stderr, flush=True)


def tree_hash(root: Path) -> str:
    """sha1 over the relative paths and bytes of every .py file under
    `root`, in sorted order."""
    h = hashlib.sha1()
    for p in sorted(root.rglob("*.py")):
        h.update(str(p.relative_to(root)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


def _save(path: Path, arrays: dict) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + ".part")
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)


def _load(path: Path, names) -> dict | None:
    if not path.exists():
        return None
    try:
        with np.load(path) as z:
            return {k: z[k] for k in names}
    except (OSError, ValueError, KeyError, zipfile.BadZipFile):
        return None


def raw_graph(spec: graphgen.GraphSpec, cache: Path) -> graphgen.RawGraph:
    key = hashlib.sha1(spec.key().encode()).hexdigest()[:16]
    path = cache / "graphs" / f"{spec.name}-raw-{key}.npz"
    arrays = _load(path, _RAW)
    if arrays is None:
        t0 = time.perf_counter()
        g = graphgen.generate(spec)
        arrays = {k: getattr(g, k) for k in _RAW}
        _save(path, arrays)
        _log(f"generated {spec.name}: {len(g.indices)} directed edges",
             t0)
    return graphgen.RawGraph(**arrays)


def check_prepared(raw: graphgen.RawGraph, prep: dict,
                   old_of_new: np.ndarray) -> None:
    """Raise if `prep` is not `raw` relabeled by `old_of_new` with
    intra-first rows (see the module docstring)."""
    n = len(raw.labels)
    if not np.array_equal(np.sort(old_of_new), np.arange(n)):
        raise RuntimeError("prepare: node tags are not a permutation")
    new_of_old = np.empty(n, np.int64)
    new_of_old[old_of_new] = np.arange(n)
    for k in ("labels", "communities"):
        if not np.array_equal(prep[k], getattr(raw, k)[old_of_new]):
            raise RuntimeError(f"prepare: {k} not carried by the relabeling")
    for k in ("train_ids", "val_ids", "test_ids"):
        if not np.array_equal(prep[k], np.sort(new_of_old[getattr(raw, k)])):
            raise RuntimeError(f"prepare: {k} not carried by the relabeling")
    # same edge set under the relabeling
    deg = np.diff(prep["indptr"])
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    dst = prep["indices"].astype(np.int64)
    keys = np.sort(old_of_new[src] * n + old_of_new[dst])
    rsrc = np.repeat(np.arange(n, dtype=np.int64), np.diff(raw.indptr))
    if not np.array_equal(keys, rsrc * n + raw.indices):
        raise RuntimeError("prepare: edges differ from the generated graph")
    # intra-first rows: within each row, intra neighbors precede inter
    comm = prep["communities"]
    intra = comm[src] == comm[dst]
    n_intra = np.bincount(src[intra], minlength=n)
    if not np.array_equal(n_intra, prep["n_intra"]):
        raise RuntimeError("prepare: n_intra disagrees with communities")
    off = np.arange(len(src)) - prep["indptr"][src]
    if not np.array_equal(intra, off < prep["n_intra"][src]):
        raise RuntimeError("prepare: adjacency rows are not intra-first")


def prepared_graph(spec: graphgen.GraphSpec, cache: Path):
    """The program's prepared `Graph` (host arrays, `features=None`)."""
    import repro
    from repro.core.reorder import prepare
    from repro.graphs.csr import Graph

    src_root = Path(list(repro.__path__)[0])
    key = hashlib.sha1(
        (spec.key() + tree_hash(src_root)).encode()).hexdigest()[:16]
    path = cache / "graphs" / f"{spec.name}-prepared-{key}.npz"
    arrays = _load(path, _PREPARED)
    if arrays is None:
        raw = raw_graph(spec, cache)
        t0 = time.perf_counter()
        n = len(raw.labels)
        tags = np.arange(n, dtype=np.float32)[:, None]   # exact below 2**24
        g = prepare(Graph(
            indptr=raw.indptr, indices=raw.indices, features=tags,
            labels=raw.labels, train_ids=raw.train_ids,
            val_ids=raw.val_ids, test_ids=raw.test_ids,
            communities=raw.communities, name=spec.name))
        arrays = {k: np.asarray(getattr(g, k)) for k in _PREPARED}
        _log(f"prepared {spec.name}", t0)
        t0 = time.perf_counter()
        check_prepared(raw, arrays, g.features[:, 0].astype(np.int64))
        _save(path, arrays)
        _log(f"checked and saved prepared {spec.name}", t0)
    return Graph(features=None, name=spec.name, **arrays)
