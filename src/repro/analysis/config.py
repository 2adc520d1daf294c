"""Scoping configuration for the analysis pass.

The lint rules are scope-sensitive: wall-clock reads are fine in a
benchmark driver but not in the deterministic batch pipeline; `float()`
on an array is fine at an epoch boundary but not inside a function the
jitted step traces through. `AnalysisConfig` carries those scopes as
explicit module-prefix lists and a per-module hot-function map, so a
violation is always attributable to a named policy decision rather than
a heuristic.

Defaults here mirror `[tool.repro_analysis]` in `pyproject.toml`; the
CLI reads the pyproject block when present so CI and local runs agree.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Tuple

# Modules whose behaviour must be a pure function of (seed, cursor):
# wall-clock reads here are deterministic-contract violations unless
# explicitly waived (e.g. the prefetch watchdog's liveness heartbeats,
# which never influence delivered data).
DETERMINISTIC_PREFIXES: Tuple[str, ...] = (
    "repro/batching/",
    "repro/pipeline/",
    "repro/sampling/",
    "repro/featcache/",
    "repro/kernels/",
)

# module (path relative to src/) -> hot-path function names. Host-sync
# idioms inside these functions stall the dispatch queue or force a
# device round-trip per call. "*" marks every function in the module as
# hot (kernel bodies, model forward). Names cover methods too (bare
# method name, class-agnostic).
HOT_FUNCTIONS: Dict[str, Tuple[str, ...]] = {
    "repro/train/gnn_loop.py": ("train_step", "eval_step", "loss_fn",
                                "keep", "_train_one", "_guard_check",
                                "run_epoch", "train_steps", "evaluate",
                                "_evaluate"),
    "repro/pipeline/builder.py": ("_fused_build", "_pad_into",
                                  "_pad_fresh", "build", "_time_us"),
    "repro/pipeline/device_order.py": ("device_epoch_order",
                                       "_order_perm", "_order_comm_rand",
                                       "_order_clustergcn", "_hash_u32"),
    "repro/pipeline/prefetch.py": ("_produce",),
    # the tracer's hot-path entry points must themselves never sync:
    # tracing is sold as zero-device-impact, so the lint bans host-sync
    # idioms inside every function a traced step calls per span
    "repro/obs/trace.py": ("span", "instant", "_emit", "__enter__",
                           "__exit__"),
    "repro/core/minibatch.py": ("_build_batch_impl", "_positions", "_dedup",
                                "_sorted_positions"),
    "repro/sampling/device.py": ("sample", "_sample_level", "_topk_mask",
                                 "_hash_rank01", "epoch_ctx"),
    "repro/featcache/dynamic.py": ("ref_updates", "with_refs",
                                   "_refill_jit", "_integrity_jit"),
    "repro/kernels/gather_agg/ops.py": ("*",),
    "repro/kernels/gather_agg/kernel.py": ("*",),
    "repro/kernels/gather_cached/ops.py": ("*",),
    "repro/kernels/gather_cached/kernel.py": ("*",),
    "repro/kernels/gather_mean/ops.py": ("*",),
    "repro/models/gnn/models.py": ("*",),
    "repro/models/gnn/fullgraph.py": ("*",),
}

# Modules that build device arrays: f64 literals/dtypes here leak into
# jaxprs (weak-type promotion or explicit casts) and double memory
# traffic on the feature path.
DEVICE_PREFIXES: Tuple[str, ...] = (
    "repro/kernels/",
    "repro/models/",
    "repro/sampling/device.py",
    "repro/pipeline/",
    "repro/featcache/dynamic.py",
    "repro/featcache/plan.py",
    "repro/train/gnn_loop.py",
)

# Host-side analytics that legitimately compute in f64 (modularity math,
# cache-simulator scores) and cast to f32 at the device boundary — the
# boundary casts are what `featcache/plan.py` tests pin.
F64_HOST_EXEMPT: Tuple[str, ...] = (
    "repro/core/community.py",
    "repro/featcache/sim.py",
    "repro/featcache/plan.py",
)

# Deprecated shims: importable for external callers during the
# deprecation window, but internal src/repro code must use the
# replacement module. The shim file itself is exempt (it re-exports).
DEPRECATED_MODULES: Dict[str, str] = {
    "repro.core.cachesim": "repro.featcache.sim",
    "repro.core.sampler": "repro.sampling",
}


@dataclass(frozen=True)
class AnalysisConfig:
    """Resolved scoping config consumed by `lint.py`."""
    deterministic_prefixes: Tuple[str, ...] = DETERMINISTIC_PREFIXES
    hot_functions: Dict[str, Tuple[str, ...]] = field(
        default_factory=lambda: dict(HOT_FUNCTIONS))
    device_prefixes: Tuple[str, ...] = DEVICE_PREFIXES
    f64_host_exempt: Tuple[str, ...] = F64_HOST_EXEMPT
    deprecated_modules: Dict[str, str] = field(
        default_factory=lambda: dict(DEPRECATED_MODULES))

    @classmethod
    def from_pyproject(cls, data: dict) -> "AnalysisConfig":
        """Build from a parsed `[tool.repro_analysis]` table; missing
        keys fall back to the module defaults above."""
        t = data.get("tool", {}).get("repro_analysis", {})
        kw = {}
        if "deterministic_prefixes" in t:
            kw["deterministic_prefixes"] = tuple(t["deterministic_prefixes"])
        if "device_prefixes" in t:
            kw["device_prefixes"] = tuple(t["device_prefixes"])
        if "f64_host_exempt" in t:
            kw["f64_host_exempt"] = tuple(t["f64_host_exempt"])
        if "hot_functions" in t:
            kw["hot_functions"] = {k: tuple(v)
                                   for k, v in t["hot_functions"].items()}
        if "deprecated_modules" in t:
            kw["deprecated_modules"] = dict(t["deprecated_modules"])
        return cls(**kw)

    # -- scope predicates (paths are relative to src/, posix separators)
    def in_deterministic(self, relpath: str) -> bool:
        return relpath.startswith(self.deterministic_prefixes)

    def in_device(self, relpath: str) -> bool:
        return (relpath.startswith(self.device_prefixes)
                and relpath not in self.f64_host_exempt)

    def hot_names(self, relpath: str) -> Tuple[str, ...]:
        return self.hot_functions.get(relpath, ())
