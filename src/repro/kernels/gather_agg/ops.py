"""Public fused gather-aggregate op with a custom VJP.

`gather_agg(x, idx, w)` computes `out[i] = sum_j w[i,j] * x[idx[i,j]]`
without ever materializing the (n_dst, r, F) gathered intermediate — in
either direction: the forward is the multi-row-tiled Pallas gather-reduce,
the backward is a Pallas scatter-add for dx plus a fused gather-dot for dw
(see `kernel.py`). `impl="jnp"` falls back to the XLA reference
(`ref.gather_agg_ref`) with native autodiff; `impl="auto"` picks the Pallas
kernel on TPU and the jnp path elsewhere (interpret mode is a simulator —
correct, but only for validation, never for CPU throughput).

Model code selects the path via `GNNConfig.agg_impl`; `resolve_agg_impl`
is the single place the "auto" policy lives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gather_agg.kernel import (gather_agg_bwd_dw_pallas,
                                             gather_agg_bwd_dx_pallas,
                                             gather_agg_fwd_pallas)
from repro.kernels.gather_agg.ref import gather_agg_ref

AGG_IMPLS = ("auto", "jnp", "pallas")


def resolve_agg_impl(impl: str) -> str:
    """'auto' -> 'pallas' on TPU backends, 'jnp' elsewhere."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"agg_impl must be one of {AGG_IMPLS}, got {impl!r}")
    return impl


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gather_agg(x, idx, w, block_dst, interpret):
    return gather_agg_fwd_pallas(x, idx, w, block_dst=block_dst,
                                 interpret=interpret)


def _gather_agg_fwd(x, idx, w, block_dst, interpret):
    out = gather_agg_fwd_pallas(x, idx, w, block_dst=block_dst,
                                interpret=interpret)
    return out, (x, idx, w)


def _gather_agg_bwd(block_dst, interpret, res, g):
    x, idx, w = res
    dx = gather_agg_bwd_dx_pallas(idx, w, g, x.shape[0],
                                  interpret=interpret)
    dw = gather_agg_bwd_dw_pallas(x, idx, g, block_dst=block_dst,
                                  interpret=interpret)
    didx = np.zeros(idx.shape, jax.dtypes.float0)
    return dx.astype(x.dtype), didx, dw.astype(w.dtype)


_gather_agg.defvjp(_gather_agg_fwd, _gather_agg_bwd)


def gather_agg(x, idx, w, *, impl: str = "pallas", block_dst: int = 0):
    """Fused `out[i] = sum_j w[i,j] * x[idx[i,j]]`; differentiable in x, w.

    x: (n_src, F) float; idx: (n_dst, r) int (clipped to [0, n_src));
    w: (n_dst, r) float. Returns (n_dst, F) float32. Call inside jit (the
    trainer's step functions already are); no jit wrapper here so the
    kernel inlines into the surrounding step.
    """
    impl = resolve_agg_impl(impl)
    if impl == "jnp":
        return gather_agg_ref(x, idx, w)
    interpret = jax.default_backend() != "tpu"
    idx = jnp.clip(idx.astype(jnp.int32), 0, x.shape[0] - 1)
    return _gather_agg(x, idx, w.astype(jnp.float32), block_dst, interpret)
