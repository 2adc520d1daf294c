"""Pallas TPU kernels: fused gather + per-edge-weighted reduce, and its
backward pair (scatter-add into dx, per-edge row dots for dw).

This is the GNN aggregation hot spot the paper's working-set argument is
about: for each destination node, gather its `r` sampled neighbors' feature
rows from HBM and reduce them under per-edge weights

    out[i] = sum_j w[i, j] * x[idx[i, j]]

One kernel therefore lowers SAGE's masked mean (w = mask / count), GCN's
symmetric-normalized weighted sum (w folds the degree normalizers), and
GAT's alpha-weighted value reduction (w = attention weights) — the weights
are always computed OUTSIDE the kernel, on (n_dst, r) scalars, so nothing
(n_dst, r, F)-shaped ever touches HBM.

TPU layout rules shape all three kernels:

* A row gather cannot be a BlockSpec: a (1, F) block breaks the (8, 128)
  tiling rule, and so does a one-row DMA into an (8, 128)-tiled buffer.
  Every table is therefore viewed as (n, 1, F) — one row per leading
  index, lane-padded to a multiple of 128 — and kept out of the pipeline
  (`memory_space=pl.ANY`); each gathered row is its own DMA into a
  (rows, 1, F) VMEM buffer, which is reshaped to (rows, F) for compute.
* Indices cannot be scalar-prefetched whole: SMEM is 1 MiB on v5e, and a
  layer of the trainer carries n_dst * r of them. Each grid step sees only
  its own tile's indices — an SMEM block of a tile-major flat array, each
  tile padded to a multiple of 1024 entries — plus the NEXT tile's block,
  so the row DMAs of tile i+1 are in flight while tile i reduces.

Forward / dw grid: one step per tile of `bd` destination rows (a multiple
of 8 on TPU). The tile's rows land in an (r, bd, 1, F) VMEM buffer, double-
buffered across steps; the weighted reduce (fwd) or the row dots against
the cotangent tile (dw) are then plain vector ops.

Backward dx grid: (source-row passes, destination tiles). The (R, F) dx
block of a pass stays resident in VMEM while every destination tile walks
its bd * r edges in order and adds w * g[i] into row idx - base with a
dynamic one-row read-modify-write — no sort, no atomics, and the sum order
is fixed. R is as many source rows as the VMEM budget allows (all of them
at the trainer's layer sizes, so usually one pass); edges outside a pass's
rows are skipped.

The same tiled gather serves `repro.kernels.gather_cached`: with two
tables, an index v >= 0 reads row v of the first and v < 0 reads row
-v - 1 of the second.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM budgets (v5e has 128 MiB of VMEM per core): the double-buffered row
# tiles of the gather, and one buffer of the resident dx block
_TILE_BYTES = 4 * 1024 * 1024
_DX_BYTES = 24 * 1024 * 1024
# a 1-D int32 array is tiled by 1024 entries, so SMEM blocks of the
# flattened indices come in multiples of it
_SMEM_ALIGN = 1024


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def dst_tile(n_dst: int, r: int, f: int, itemsize: int = 4) -> int:
    """Destination rows per grid step: the largest multiple of 8 (at most
    256) whose double-buffered (r, bd, F) row tile fits `_TILE_BYTES`."""
    bd = _TILE_BYTES // (2 * r * _round_up(f, 128) * itemsize)
    bd = max(8, min(256, bd // 8 * 8))
    return min(bd, _round_up(n_dst, 8))


def dx_block_rows(n_src: int, f: int) -> int:
    """Source rows per pass of the dx kernel: all of them, or as many
    (a multiple of 8) as `_DX_BYTES` holds at F lane-padded to 128."""
    return min(_round_up(n_src, 8),
               _DX_BYTES // (_round_up(f, 128) * 4) // 8 * 8)


def row_table(x):
    """(n, F) -> the (n, 1, F') row view the kernels DMA from, F' = F
    lane-padded to a multiple of 128."""
    n, f = x.shape
    fp = _round_up(f, 128)
    if fp != f:
        x = jnp.pad(x, ((0, 0), (0, fp - f)))
    return x.reshape(n, 1, fp)


def _tile_major(a, bd: int, fill=0):
    """(D, r) per-edge array -> flat tile-major (n * L,), each tile of
    bd * r entries padded to L (a multiple of `_SMEM_ALIGN`)."""
    D, r = a.shape
    n = -(-D // bd)
    L = _round_up(bd * r, _SMEM_ALIGN)
    a = jnp.pad(a, ((0, n * bd - D), (0, 0)), constant_values=fill)
    return jnp.pad(a.reshape(n, bd * r), ((0, 0), (0, L - bd * r)),
                   constant_values=fill).reshape(-1), n, L


def _row_copy(table, row, buf, sem):
    """DMA row `row` of an (n, 1, F) table into a (1, F) buffer slot."""
    return pltpu.make_async_copy(table.at[row], buf, sem)


def _start_rows(idx_ref, tables, buf, sem, *, bd: int, r: int):
    """buf[j, ii] <- row idx[ii * r + j] for the tile's bd * r edges; with
    two tables a negative index v selects row -v - 1 of the second."""
    def body(ii, carry):
        for j in range(r):
            v = idx_ref[ii * r + j]
            dst = buf.at[j, ii]
            if len(tables) == 1:
                _row_copy(tables[0], v, dst, sem).start()
                continue

            @pl.when(v >= 0)
            def _first():
                _row_copy(tables[0], v, dst, sem).start()

            @pl.when(v < 0)
            def _second():
                _row_copy(tables[1], -v - 1, dst, sem).start()
        return carry
    jax.lax.fori_loop(0, bd, body, 0)


def _wait_rows(table, buf, sem, *, bd: int, r: int):
    def body(ii, carry):
        for j in range(r):
            _row_copy(table, 0, buf.at[j, ii], sem).wait()
        return carry
    jax.lax.fori_loop(0, bd, body, 0)


def _gather_tile_kernel(idx_ref, nidx_ref, *refs, bd: int, r: int,
                        n_tables: int, combine):
    """Gather this tile's bd * r rows (prefetching the next tile's) and
    hand them, as r (bd, F) float32 values, to `combine(rows, t_ref,
    o_ref)`; `t_ref` is the tile's block of the per-destination operand,
    or None."""
    *t_ref, o_ref, buf, sem = refs
    t_ref, tables = t_ref[:-n_tables], t_ref[-n_tables:]
    i = pl.program_id(0)
    slot = i % 2

    @pl.when(i == 0)
    def _first():
        _start_rows(idx_ref, tables, buf.at[0], sem.at[0], bd=bd, r=r)

    @pl.when(i + 1 < pl.num_programs(0))
    def _prefetch():
        _start_rows(nidx_ref, tables, buf.at[1 - slot], sem.at[1 - slot],
                    bd=bd, r=r)

    _wait_rows(tables[0], buf.at[slot], sem.at[slot], bd=bd, r=r)
    fp = buf.shape[-1]
    rows = [buf[slot, j].reshape(bd, fp).astype(jnp.float32)
            for j in range(r)]
    combine(rows, t_ref[0] if t_ref else None, o_ref)


def gather_tiles(tables, idx, t, combine, out_cols: int, *, name: str,
                 block_dst: int = 0, interpret: bool = False):
    """Run `combine` over destination tiles of gathered rows.

    tables: one (n, F) table, or two of one width and dtype (see the
    module docstring for the index rule); idx: (D, r) int32 rows; t: a
    (D, c) float32 per-destination operand blocked with the tile, or None.
    `name` names the kernel and its scope in a device trace. Returns
    (D, out_cols) float32."""
    D, r = idx.shape
    views = [row_table(x) for x in tables]
    fp = views[0].shape[-1]
    dtype = views[0].dtype
    bd = block_dst or dst_tile(D, r, fp, dtype.itemsize)
    # padded rows/slots gather row 0 (with weight 0 where t carries one)
    flat, n, L = _tile_major(idx.astype(jnp.int32), bd)
    smem = functools.partial(pl.BlockSpec, (L,), memory_space=pltpu.SMEM)
    in_specs = [smem(lambda i: (i,)),
                smem(lambda i: (jnp.minimum(i + 1, n - 1),))]
    args = [flat, flat]
    if t is not None:
        in_specs.append(pl.BlockSpec((bd, t.shape[1]), lambda i: (i, 0)))
        args.append(jnp.pad(t, ((0, n * bd - D), (0, 0))))
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(views)
    call = pl.pallas_call(
        functools.partial(_gather_tile_kernel, bd=bd, r=r,
                          n_tables=len(views), combine=combine),
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bd, out_cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n * bd, out_cols), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, r, bd, 1, fp), dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        # step i+1's rows are started by step i: the grid is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )
    # the scope holds the kernel alone, so a trace's time under it is the
    # kernel's
    with jax.named_scope(name):
        out = call(*args, *views)
    return out[:D]


# ---------------------------------------------------------------------------
# forward: out[i] = sum_j w[i, j] * x[idx[i, j]]
# ---------------------------------------------------------------------------
def _weighted_sum(rows, w_ref, o_ref):
    acc = rows[0] * w_ref[:, 0:1]
    for j in range(1, len(rows)):
        acc += rows[j] * w_ref[:, j:j + 1]
    o_ref[...] = acc[:, :o_ref.shape[1]]


def gather_agg_fwd_pallas(x, idx, w, *, block_dst: int = 0,
                          interpret: bool = False):
    """x: (n_src, F); idx: (n_dst, r) int32 in [0, n_src); w: (n_dst, r)
    float32. Returns (n_dst, F) float32. block_dst=0 sizes the tile by
    `dst_tile`; on a TPU an explicit block_dst must be a multiple of 8."""
    return gather_tiles((x,), idx, w, _weighted_sum, x.shape[1],
                        name="gather_agg_fwd", block_dst=block_dst,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# backward dw: dw[i, j] = <g[i], x[idx[i, j]]>
# ---------------------------------------------------------------------------
def _row_dots(rows, g_ref, o_ref):
    g = g_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j, row in enumerate(rows):
        acc = jnp.where(lane == j,
                        jnp.sum(row * g, axis=1, keepdims=True), acc)
    o_ref[...] = acc


def gather_agg_bwd_dw_pallas(x, idx, g, *, block_dst: int = 0,
                             interpret: bool = False):
    """Per-edge weight cotangents (needed when w carries gradient, e.g. GAT
    attention): the forward's tiled gather, then a row dot against the
    tile's cotangent rows. Returns (n_dst, r) float32. Dead-code-eliminated
    by XLA when dw is unused (SAGE/GCN)."""
    f = g.shape[1]
    g = jnp.pad(g.astype(jnp.float32), ((0, 0), (0, _round_up(f, 128) - f)))
    return gather_tiles((x,), idx, g, _row_dots, idx.shape[1],
                        name="gather_agg_dw", block_dst=block_dst,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# backward dx: dx[idx[i, j]] += w[i, j] * g[i]
# ---------------------------------------------------------------------------
def _bwd_dx_kernel(idx_ref, w_ref, g_ref, o_ref, *, bd: int, r: int,
                   one_pass: bool):
    rows = o_ref.shape[0]
    base = pl.program_id(0) * rows

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def body(ii, carry):
        g = g_ref[pl.ds(ii, 1), :]
        for j in range(r):
            s = idx_ref[ii * r + j] - base
            w = w_ref[ii * r + j]

            def add(s=s, w=w):
                o_ref[pl.ds(s, 1), :] += g * w

            if one_pass:
                add()
            else:
                pl.when((s >= 0) & (s < rows))(add)
        return carry

    jax.lax.fori_loop(0, bd, body, 0)


def gather_agg_bwd_dx_pallas(idx, w, g, n_src: int, *,
                             interpret: bool = False):
    """Scatter-add cotangents back to the gathered rows.

    idx/w: (n_dst, r); g: (n_dst, F) cotangent. Returns (n_src, F) float32
    (see the module docstring for the grid)."""
    D, r = idx.shape
    f = g.shape[1]
    fp = _round_up(f, 128)
    bd = min(256, _round_up(D, 8))
    rows = dx_block_rows(n_src, f)
    passes = -(-n_src // rows)
    idx, n, L = _tile_major(idx.astype(jnp.int32), bd)
    w, _, _ = _tile_major(w.astype(jnp.float32), bd)     # padding: w = 0
    g = jnp.pad(g.astype(jnp.float32), ((0, n * bd - D), (0, fp - f)))
    smem = functools.partial(pl.BlockSpec, (L,), lambda p, i: (i,),
                             memory_space=pltpu.SMEM)
    call = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, bd=bd, r=r, one_pass=passes == 1),
        grid=(passes, n),
        in_specs=[smem(), smem(),
                  pl.BlockSpec((bd, fp), lambda p, i: (i, 0))],
        out_specs=pl.BlockSpec((rows, fp), lambda p, i: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((passes * rows, fp), jnp.float32),
        # the dx block accumulates across the destination axis
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * rows * fp * 4 + _TILE_BYTES + (8 << 20)),
        interpret=interpret,
        name="gather_agg_dx",
    )
    with jax.named_scope("gather_agg_dx"):
        out = call(idx, w, g)
    return out[:n_src, :f]
