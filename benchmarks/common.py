"""Shared benchmark helpers. All batch construction flows through
`repro.batching`: policies come from the registry and caps from a shared
`CapsCalibrator` whose JSON cache under `artifacts/` lets repeated sweeps
skip the numpy calibration probe."""
from __future__ import annotations

import functools
import os
import time

import numpy as np

from repro.batching import CapsCalibrator, make_policy, root_batches
from repro.configs.base import GNNConfig, TrainConfig
from repro.core.reorder import prepare
from repro.graphs import synthetic
from repro.runtime import use_compile_cache

# every benchmark script imports this module first
use_compile_cache()

POLICIES = {
    "RAND-ROOTS/p0.5": make_policy("rand"),
    "NORAND-ROOTS/p1.0": make_policy("norand"),
    "COMM-RAND-MIX-0%/p1.0": make_policy("comm_rand", mix=0.0, p=1.0),
    "COMM-RAND-MIX-12.5%/p1.0": make_policy("comm_rand", mix=0.125, p=1.0),
    "COMM-RAND-MIX-25%/p1.0": make_policy("comm_rand", mix=0.25, p=1.0),
    "COMM-RAND-MIX-50%/p1.0": make_policy("comm_rand", mix=0.5, p=1.0),
}

CAPS_CACHE = os.path.join(os.path.dirname(__file__), "artifacts",
                          "caps_cache.json")


def calibrator(seed: int = 0) -> CapsCalibrator:
    """Disk-cached calibrator shared by every GNN benchmark driver."""
    return CapsCalibrator(cache_path=CAPS_CACHE, seed=seed)


def epoch_batches(g, policy, batch_size: int, seed: int = 0) -> np.ndarray:
    """One epoch of root-id batches through the `repro.batching` API."""
    return root_batches(g, policy, batch_size, seed=seed)


@functools.lru_cache(maxsize=None)
def dataset(name: str):
    return prepare(synthetic.load(name), oracle=True)


def gnn_cfg(g, layers=2, hidden=64, fanout=(10, 10)) -> GNNConfig:
    return GNNConfig(f"sage-{g.name}", "sage", layers, hidden, g.feat_dim,
                     g.num_classes, fanout=fanout)


def quick_tcfg(max_epochs=15, batch=512) -> TrainConfig:
    return TrainConfig(batch_size=batch, max_epochs=max_epochs,
                       early_stop_patience=5)


def timer_us(fn, *args, warmup=1, iters=3) -> float:
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def emit(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")


def measured_static_miss(plan, stream) -> dict:
    """Replay a host access stream through the DEVICE hit counters of a
    `repro.featcache.CachePlan` — the measured (not simulated) numbers
    fig9/fig10 report next to the LRU simulation.

    Returns {"miss_rate", "miss_per_batch"}. miss_per_batch (missed rows
    per batch = feature rows actually fetched from the global matrix) is
    the HBM-traffic quantity behind the paper's Fig-10 speedups and the
    one the drivers assert orderings on: the per-ACCESS rate divides by
    each policy's own footprint, normalizing away exactly the working-set
    reduction COMM-RAND exists to create."""
    import jax.numpy as jnp

    from repro import featcache
    h = m = nb = 0
    for ids in stream:
        hh, mm = featcache.cache_stats(
            plan.pos, jnp.asarray(ids, jnp.int32), plan.pos.shape[0])
        h += int(hh)
        m += int(mm)
        nb += 1
    return {"miss_rate": 1.0 - h / max(h + m, 1),
            "miss_per_batch": m / max(nb, 1)}


def measured_dynamic_miss(plan, stream, feats, epochs: int = 2) -> dict:
    """Measured numbers of the DYNAMIC CLOCK cache (`featcache.dynamic`)
    over a host access stream: seed the state from `plan`, replay the
    stream for `epochs` passes feeding the reference-bit/frequency
    accumulators exactly like the trainer's steps do, run the
    epoch-boundary refill between passes, and report the LAST pass — the
    steady-state analogue of the trainer's per-epoch measurement. Pass 1
    is bit-identical to the static plan (same residency); the refill then
    re-admits against the distribution the cache ACTUALLY served, which
    is the paper's dynamic-cache story and why the measured
    missed-rows-per-batch can only improve on the static plan when the
    stream repeats. Returns {"miss_rate", "miss_per_batch", "admitted"}."""
    import jax.numpy as jnp

    from repro import featcache
    from repro.featcache import dynamic

    state = dynamic.from_plan(plan)
    feats = jnp.asarray(feats)
    admitted = 0
    h = m = nb = 0
    for e in range(epochs):
        h = m = nb = 0
        for ids in stream:
            d = jnp.asarray(ids, jnp.int32)
            hh, mm = featcache.cache_stats(state.pos, d,
                                           state.pos.shape[0])
            state = dynamic.with_refs(state, dynamic.ref_updates(state, d))
            h += int(hh)
            m += int(mm)
            nb += 1
        if e < epochs - 1:
            state, adm = dynamic.refill(state, feats)
            admitted += int(adm)
    return {"miss_rate": 1.0 - h / max(h + m, 1),
            "miss_per_batch": m / max(nb, 1),
            "admitted": admitted}


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(_REPO_ROOT, "BENCH_kernels.json")
BENCH_CACHE_JSON = os.path.join(_REPO_ROOT, "BENCH_cache.json")


def write_bench_json(entries: dict, path: str = BENCH_JSON) -> None:
    """Merge `entries` into a machine-readable bench artifact at the repo
    root (BENCH_kernels.json by default; fig9/fig10 target
    BENCH_cache.json) — the perf trajectory future PRs diff against.
    Existing keys from other bench drivers are preserved."""
    import json

    from repro.obs.metrics import run_metadata
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data.update(entries)
    # shared run-metadata header (repro.obs): schema/backend/jax/
    # git_commit/hostname — CI asserts these keys on every artifact
    data["_meta"] = dict(
        run_metadata(),
        note="off-TPU, pallas runs in interpret mode: "
             "us timings there are shape-validation only; "
             "compare the analytic hbm_bytes")
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
