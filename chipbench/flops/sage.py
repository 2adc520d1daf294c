"""GraphSAGE operation counts from a batch's real counts.

`counts` holds `n[h]`, the real (non-padding) unique nodes of level h
(h = 0 is the roots, L the input level), and `e[h]`, the real sampled
edges of hop h (dst level h, src level h+1). Layer i (0 = input side)
runs hop L-1-i. A multiply-add counts 2. Counted: the two matmuls per
layer and the neighbor aggregation, forward and backward (weight
gradients; input gradients except at layer 0, whose input is the
feature matrix). Elementwise work (bias, ReLU, dropout, loss) and
recomputation are not counted.
"""
from __future__ import annotations


def _layers(cfg: dict, counts: dict):
    L = cfg["num_layers"]
    d = [cfg["in_dim"]] + [cfg["hidden_dim"]] * (L - 1) + [cfg["num_classes"]]
    for i in range(L):
        h = L - 1 - i
        yield i, counts["n"][h + 1], counts["n"][h], counts["e"][h], \
            d[i], d[i + 1]


def step_flops(cfg: dict, counts: dict) -> float:
    total = 0.0
    for i, n_src, n_dst, e, f_in, f_out in _layers(cfg, counts):
        mm = 2 * 2.0 * n_dst * f_in * f_out      # self and neighbor matmul
        agg = 2.0 * e * f_in
        total += mm + agg                         # forward
        total += mm                               # weight gradients
        if i > 0:
            total += mm + agg                     # input gradients
    return total


def gather_agg_calls(cfg: dict, counts: dict) -> list:
    """(kind, edges, width, dst rows, src rows) of every `gather_agg`
    kernel call in one step: the forward per layer and the dx scatter
    for every layer but the first (its input carries no gradient; the
    dw kernel is dead code, as SAGE's weights carry none)."""
    calls = []
    for i, n_src, n_dst, e, f_in, _ in _layers(cfg, counts):
        calls.append(("fwd", e, f_in, n_dst, n_src))
        if i > 0:
            calls.append(("dx", e, f_in, n_dst, n_src))
    return calls
