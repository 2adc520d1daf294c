"""Roofline of the fused gather-aggregate kernels (`kernels/gather_agg`).

The least time of a call is the larger of its operations over the chip's
peak FLOP/s and its bytes over the peak HBM bandwidth. Operations and
bytes are counted from the real edges and rows at the logical width F,
in float32, so lane padding and padded slots show as lost share:

    fwd  out[i] = sum_j w[i,j] x[idx[i,j]]: 2EF operations; reads E rows,
         E indices and E weights, writes the D output rows
    dw   dw[i,j] = <g[i], x[idx[i,j]]>: 2EF; reads E rows, E indices and
         the D cotangent rows, writes E weights
    dx   dx[idx[i,j]] += w[i,j] g[i]: 2EF; reads the D cotangent rows,
         E indices and E weights, writes the S source rows once
"""
from __future__ import annotations

# how the kernels' ops read in a TPU trace: the HLO text of a Mosaic
# custom call (`%jvp__.3 = f32[...] custom-call(...),
# custom_call_target="tpu_custom_call"`; forward and dw under `jvp`, dx
# under `transpose_jvp`). On the timed path the gather_agg calls are the
# only Mosaic kernels (checked by hand on a v5e trace, see PERF.md); the
# kernels carry no name of their own in the trace yet.
KERNELS = ('custom_call_target="tpu_custom_call"',)
ITEM = 4


def flops_bytes(kind: str, e, f, n_dst, n_src):
    flops = 2.0 * e * f
    if kind == "fwd":
        nbytes = ITEM * (e * f + 2 * e + n_dst * f)
    elif kind == "dw":
        nbytes = ITEM * (e * f + 2 * e + n_dst * f)
    elif kind == "dx":
        nbytes = ITEM * (n_dst * f + 2 * e + n_src * f)
    else:
        raise ValueError(kind)
    return flops, float(nbytes)


def least_time_s(calls, peaks: dict) -> float:
    t = 0.0
    for kind, e, f, n_dst, n_src in calls:
        fl, by = flops_bytes(kind, e, f, n_dst, n_src)
        t += max(fl / peaks["bf16_flops_per_s"],
                 by / peaks["hbm_bytes_per_s"])
    return t
