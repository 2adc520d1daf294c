"""FSDPxTP sharding rules + activation constraints.

One module owns every sharding decision:

  * `param_specs` / `param_shardings` — name-based PartitionSpecs for the
    transformer param tree (column-parallel up-projections, row-parallel
    down-projections, vocab-parallel embedding/head, expert-parallel MoE).
  * `batch_specs` / `cache_specs` — input and KV-cache layouts per strategy
    ("fsdp" for training, "tp_sp" for serving).
  * the `act_*` family — activation sharding constraints the model code
    sprinkles on residuals / heads / MoE dispatch. They are NO-OPS outside a
    `use_mesh` context, so the same model code runs single-device CPU smoke
    tests and the 512-chip dry-run.

Every proposed spec passes through `_fit`, a divisibility filter: a mesh
axis that does not evenly divide its dimension is dropped (that dim stays
replicated) instead of erroring. This is what lets e.g. a (B, 1, d) decode
residual reuse the sequence-parallel train spec, or a 1-KV-head model skip
head sharding, without per-arch special cases.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# mesh axes that carry the (pure or fully-sharded) data-parallel dimension
_DATA_AXES = ("pod", "data")
_MODEL_AXIS = "model"


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


class ShardCtx:
    """Resolved sharding context for one mesh + strategy.

    strategy: "fsdp" (training: batch over data axes, params FSDP-sharded)
              "tp_sp" (serving: tensor-parallel with sequence-parallel
              residuals). Activation constraints consult the active ctx.
    """

    def __init__(self, mesh: Mesh, strategy: Optional[str] = None):
        self.mesh = mesh
        self.strategy = strategy or "fsdp"
        sizes = _axis_sizes(mesh)
        self.data_axes: Tuple[str, ...] = tuple(
            a for a in mesh.axis_names if a in _DATA_AXES)
        self.model_axis = _MODEL_AXIS if _MODEL_AXIS in sizes else None
        self.fsdp = int(np.prod([sizes[a] for a in self.data_axes])) \
            if self.data_axes else 1
        self.tp = int(sizes.get(_MODEL_AXIS, 1))

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        """PartitionSpec entry for a batch dimension."""
        return self.data_axes

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)


_STATE = threading.local()


def active() -> Optional[ShardCtx]:
    """The innermost `use_mesh` context, or None (constraints no-op)."""
    stack = getattr(_STATE, "stack", None)
    return stack[-1] if stack else None


@contextmanager
def use_mesh(mesh: Mesh, strategy: Optional[str] = None):
    """Activate `shd` constraints for code traced inside the block."""
    ctx = ShardCtx(mesh, strategy)
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    stack.append(ctx)
    try:
        yield ctx
    finally:
        stack.pop()


# ---------------------------------------------------------------------------
# divisibility filter
# ---------------------------------------------------------------------------
def _fit(entries, shape, mesh: Mesh) -> P:
    """Drop any spec entry whose mesh-axis product does not divide the dim."""
    sizes = _axis_sizes(mesh)
    used = set()
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in sizes and a not in used)
        n = int(np.prod([sizes[a] for a in axes])) if axes else 1
        if not axes or n <= 1 or dim % n != 0:
            out.append(None)
            continue
        used.update(axes)
        out.append(axes if len(axes) > 1 else axes[0])
    return P(*out)


def _spec_fits(entries, shape, mesh) -> bool:
    fitted = _fit(entries, shape, mesh)
    return tuple(fitted) == tuple(
        e if not (isinstance(e, tuple) and len(e) == 1) else e[0]
        for e in entries)


def to_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------
# column-parallel (output dim over TP) / row-parallel (input dim over TP)
_COL = {"wq", "wk", "wv", "wg", "wu", "w1", "swg", "swu",
        "wr_t", "wk_t", "wv_t", "wg_t", "wck", "in_proj"}
_ROW = {"wo", "wd", "w2", "swd", "wcv", "out_proj"}
# stacked-subtree markers: leaves below these have a leading layer axis
_STACKED = {"layers", "enc_layers"}


def _path_names(kp) -> list:
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in kp]


def _leaf_spec(kp, leaf, mesh: Mesh) -> P:
    parts = _path_names(kp)
    name = parts[-1] if parts else ""
    shape = tuple(leaf.shape)
    data = tuple(a for a in mesh.axis_names if a in _DATA_AXES)
    data_entry = data if len(data) > 1 else (data[0] if data else None)
    lead = [None] if any(p in _STACKED for p in parts) else []
    nd = len(shape) - len(lead)

    if name == "embed":                      # (V, d): vocab-parallel
        entries = ["model", data_entry]
    elif name == "head":                     # (d, V): vocab-parallel out
        entries = [data_entry, "model"]
    elif name == "pos_embed":
        entries = [None, data_entry]
    elif nd == 3 and name in ("wg", "wu", "wd") and "moe" in parts:
        entries = lead + ["model", data_entry, None]   # expert-parallel
    elif nd == 2 and name in _COL:
        entries = lead + [data_entry, "model"]
    elif nd == 2 and name in _ROW:
        entries = lead + ["model", data_entry]
    else:                                    # norms, biases, small matrices
        entries = lead + [None] * nd
    return _fit(entries, shape, mesh)


def param_specs(params: Any, mesh: Mesh) -> Any:
    """PartitionSpec per leaf of a (possibly abstract) param tree."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, x: _leaf_spec(kp, x, mesh), params)


def param_shardings(params: Any, mesh: Mesh) -> Any:
    """NamedSharding per leaf (for device_put / jit out_shardings)."""
    return to_shardings(param_specs(params, mesh), mesh)


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------
def batch_specs(batch: Any, mesh: Mesh, strategy: str = "fsdp") -> Any:
    """Batch-dim-sharded specs for an input tree (tokens/labels/frames/...).

    Both strategies shard dim 0 over the data axes; the filter replicates
    anything that does not divide (e.g. global_batch=1 long-context decode).
    """
    ctx = ShardCtx(mesh, strategy)
    entry = ctx.batch_axes if ctx.batch_axes else None

    def leaf(x):
        shape = tuple(x.shape)
        return _fit([entry] + [None] * (len(shape) - 1), shape, mesh)

    return jax.tree.map(leaf, batch)


def cache_specs(cache: Any, mesh: Mesh) -> Any:
    """KV/state cache specs: batch over data axes, heads over TP.

    k/v/ck/cv are (L, B, S, KH, hd): shard KH over `model`; when KH does not
    divide (GQA models with few KV heads), fall back to sharding S instead
    so the cache still distributes. rwkv state s is (L, B, H, hd, hd).
    """
    ctx = ShardCtx(mesh, None)
    b = ctx.batch_axes if ctx.batch_axes else None

    def leaf(kp, x):
        name = _path_names(kp)[-1] if kp else ""
        shape = tuple(x.shape)
        if name in ("k", "v", "ck", "cv") and len(shape) == 5:
            primary = [None, b, None, "model", None]
            if _spec_fits(primary, shape, mesh):
                return _fit(primary, shape, mesh)
            return _fit([None, b, "model", None, None], shape, mesh)
        if name == "s" and len(shape) == 5:
            return _fit([None, b, "model", None, None], shape, mesh)
        return _fit([None, b] + [None] * (len(shape) - 2), shape, mesh)

    return jax.tree_util.tree_map_with_path(leaf, cache)


# ---------------------------------------------------------------------------
# activation constraints (no-ops outside `use_mesh`)
# ---------------------------------------------------------------------------
def _constrain(x, entries):
    ctx = active()
    if ctx is None or not hasattr(x, "ndim") or x.ndim != len(entries):
        return x
    spec = _fit(entries, x.shape, ctx.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def act_tokens(x):
    """(B, S) int tokens: batch-sharded."""
    ctx = active()
    if ctx is None:
        return x
    return _constrain(x, [ctx.batch_axes, None])


def act_residual(x):
    """(B, S, d) residual stream: batch + sequence-parallel over TP."""
    ctx = active()
    if ctx is None:
        return x
    return _constrain(x, [ctx.batch_axes, ctx.model_axis, None])


def act_partial_out(x):
    """Pre-residual block output: same layout as the residual so the TP
    reduction lowers as reduce-scatter into the sequence-parallel shard."""
    return act_residual(x)


def act_heads(x):
    """(B, S, H, hd) attention tensors: heads over TP."""
    ctx = active()
    if ctx is None:
        return x
    return _constrain(x, [ctx.batch_axes, None, ctx.model_axis, None])


def act_ce_hidden(x):
    """(B, C, d) CE chunk hidden: batch-sharded, gathered over TP."""
    ctx = active()
    if ctx is None:
        return x
    return _constrain(x, [ctx.batch_axes, None, None])


def act_logits(x):
    """(B, C, V) CE chunk logits: vocab-parallel."""
    ctx = active()
    if ctx is None:
        return x
    return _constrain(x, [ctx.batch_axes, None, ctx.model_axis])


def act_moe_grouped(x):
    """(G, ...) token-grouped MoE tensors: group axis over EVERY mesh axis
    so dispatch/combine scatters stay device-local."""
    ctx = active()
    if ctx is None:
        return x
    return _constrain(x, [ctx.all_axes] + [None] * (x.ndim - 1))


def act_moe_dispatch(x):
    """(G, E, C, d)-style expert-slotted tensors: experts over TP (the
    group-axis reshard on entry/exit is the EP all-to-all)."""
    ctx = active()
    if ctx is None:
        return x
    return _constrain(
        x, [ctx.batch_axes, ctx.model_axis] + [None] * (x.ndim - 2))
