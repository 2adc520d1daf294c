"""Fault-tolerant checkpointing: atomic, keep-N, verified resume-latest.

Layout:  <dir>/step_<N>/manifest.json + leaf_<i>.npy (one per pytree leaf).
Writes go to a temp directory then os.rename (atomic on POSIX) — a crash
mid-save never corrupts the latest checkpoint, and `_gc` sweeps any
`.tmp_save_*` litter such a crash leaves behind. Restore optionally
re-shards onto a (possibly different-sized) mesh — the elastic-restart
path.

Integrity: every leaf is checksummed (CRC32 of the raw array bytes) into
the manifest at save time. `restore` verifies manifest parse, leaf
presence, shape/dtype, and checksum, raising `CheckpointCorrupt` on any
mismatch; `restore_latest` walks checkpoints newest-to-oldest and falls
back past corrupt/partial ones to the newest VALID step instead of
crashing — torn writes, bit rot, and half-deleted directories cost at
most `keep - 1` steps of progress, never the run. Checkpoints written
before checksums existed restore fine (verification of a missing `crc32`
field is skipped).

Fault injection: `repro.resilience` arms the `ckpt_truncate` site here —
`save` deterministically corrupts the checkpoint it just wrote, which is
exactly the failure `restore_latest`'s fallback must absorb.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import tokenize
import warnings
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.obs import trace as obs_trace
from repro.resilience import faults


class CheckpointCorrupt(RuntimeError):
    """A checkpoint directory failed verification (missing/truncated
    files, checksum or shape mismatch, unparseable manifest)."""


def _flatten_with_paths(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    paths = [jax.tree_util.keystr(kp) for kp, _ in
             jax.tree_util.tree_flatten_with_path(tree)[0]]
    return leaves, paths, treedef


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def _step_dirs(ckpt_dir: str) -> List[Tuple[int, str]]:
    """(step, dirname) for every well-formed step_* entry, ascending.
    Malformed names (step_garbage) and `.tmp_save_*` litter are skipped
    rather than crashing `int(...)`."""
    out = []
    for d in os.listdir(ckpt_dir):
        if not d.startswith("step_"):
            continue
        try:
            out.append((int(d.split("_", 1)[1]), d))
        except ValueError:
            continue
    return sorted(out)


def save(ckpt_dir: str, step: int, tree: Any, extra: Optional[Dict] = None,
         keep: int = 3) -> str:
    """Atomically write `tree` (+ JSON-able `extra`) as step `step`."""
    # cat="sync": np.asarray below drains every device leaf to host —
    # this is one of the trainer's sanctioned boundary syncs
    with obs_trace.span("ckpt_save", cat="sync", step=step):
        return _save(ckpt_dir, step, tree, extra, keep)


def _save(ckpt_dir: str, step: int, tree: Any, extra: Optional[Dict],
          keep: int) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    leaves, paths, _ = _flatten_with_paths(tree)
    tmp = tempfile.mkdtemp(dir=ckpt_dir, prefix=".tmp_save_")
    try:
        manifest = {"step": step, "extra": extra or {}, "leaves": []}
        for i, (leaf, path) in enumerate(zip(leaves, paths)):
            arr = np.asarray(leaf)
            np.save(os.path.join(tmp, f"leaf_{i}.npy"), arr)
            manifest["leaves"].append(
                {"i": i, "path": path, "shape": list(arr.shape),
                 "dtype": str(arr.dtype), "crc32": _crc(arr)})
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        final = os.path.join(ckpt_dir, f"step_{step:09d}")
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    spec = faults.fire("ckpt_truncate", step=step)
    if spec is not None:
        # chaos site: damage the checkpoint we just wrote (torn write /
        # bit rot) — restore_latest must fall back past it
        faults.corrupt_checkpoint(final, faults.active().payload_rng(spec))
    _gc(ckpt_dir, keep)
    return final


def _gc(ckpt_dir: str, keep: int):
    steps = _step_dirs(ckpt_dir)
    for _, d in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)
    for d in os.listdir(ckpt_dir):
        # a crash between mkdtemp and rename leaves .tmp_save_* litter;
        # our own tmp dir is already renamed away by the time _gc runs
        if d.startswith(".tmp_save_"):
            shutil.rmtree(os.path.join(ckpt_dir, d), ignore_errors=True)


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = _step_dirs(ckpt_dir)
    return steps[-1][0] if steps else None


def restore(ckpt_dir: str, step: int, like: Any,
            shardings: Any = None) -> tuple:
    """Restore into the structure of `like`, verifying the manifest and
    every leaf (presence, shape/dtype, CRC32) — raises
    `CheckpointCorrupt` instead of returning silently wrong state. If
    `shardings` is given each leaf is device_put with its sharding (the
    elastic reshard happens here)."""
    with obs_trace.span("ckpt_restore", cat="ckpt", step=step):
        return _restore(ckpt_dir, step, like, shardings)


def _restore(ckpt_dir: str, step: int, like: Any, shardings: Any) -> tuple:
    path = os.path.join(ckpt_dir, f"step_{step:09d}")
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        refs = manifest["leaves"]
    except (OSError, ValueError, KeyError, TypeError) as e:
        raise CheckpointCorrupt(f"{path}: unreadable manifest: {e}") from e
    leaves, _, treedef = _flatten_with_paths(like)
    if len(leaves) != len(refs):
        raise CheckpointCorrupt(
            f"{path}: leaf count mismatch: restore target has "
            f"{len(leaves)}, manifest has {len(refs)}")
    out = []
    shard_leaves = (jax.tree_util.tree_flatten(shardings)[0]
                    if shardings is not None else [None] * len(leaves))
    for i, ref in enumerate(leaves):
        try:
            arr = np.load(os.path.join(path, f"leaf_{i}.npy"))
        # a corrupt .npy header is parsed as a Python literal, so it can
        # also raise SyntaxError or tokenize.TokenError
        except (OSError, ValueError, EOFError, SyntaxError,
                tokenize.TokenError) as e:
            raise CheckpointCorrupt(
                f"{path}: leaf_{i}.npy unreadable: {e}") from e
        meta = refs[i]
        if tuple(arr.shape) != tuple(meta.get("shape", arr.shape)) or \
                str(arr.dtype) != meta.get("dtype", str(arr.dtype)):
            raise CheckpointCorrupt(
                f"{path}: leaf {i} shape/dtype {arr.shape}/{arr.dtype} "
                f"!= manifest {meta.get('shape')}/{meta.get('dtype')}")
        if "crc32" in meta and _crc(arr) != meta["crc32"]:
            raise CheckpointCorrupt(f"{path}: leaf {i} checksum mismatch")
        if tuple(arr.shape) != tuple(ref.shape):
            raise CheckpointCorrupt(
                f"{path}: shape mismatch at leaf {i}: {arr.shape} vs "
                f"{ref.shape}")
        if shard_leaves[i] is not None:
            out.append(jax.device_put(arr, shard_leaves[i]))
        else:
            out.append(jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out), manifest["extra"]


def restore_latest(ckpt_dir: str, like: Any, shardings: Any = None,
                   on_corrupt: Optional[Callable[[int, Exception],
                                                 None]] = None):
    """Restore the newest VALID checkpoint, falling back past corrupt or
    partial ones (each skip warns and invokes `on_corrupt(step, err)` for
    metering). Returns (None, None, None) when no valid checkpoint
    exists — same as an empty directory."""
    if not os.path.isdir(ckpt_dir):
        return None, None, None
    for step, _ in reversed(_step_dirs(ckpt_dir)):
        try:
            tree, extra = restore(ckpt_dir, step, like, shardings)
        except CheckpointCorrupt as e:
            warnings.warn(f"skipping corrupt checkpoint step {step}: {e}",
                          RuntimeWarning, stacklevel=2)
            if on_corrupt is not None:
                on_corrupt(step, e)
            continue
        return step, tree, extra
    return None, None, None
