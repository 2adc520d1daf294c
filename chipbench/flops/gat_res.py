"""Operation counts of GAT with linear skips and averaged output heads
(`reference/gat_res.py`) from a batch's real counts (see `sage.py` for
`counts` and the layer/hop numbering).

Per layer with H heads of width dh (hidden layers: `hidden_dim // H`,
heads concatenated; the last layer: the class count, heads averaged),
HD = H * dh: the projection `z = h @ W` over the source rows, the
per-source and per-destination attention scores, the alpha-weighted
aggregation over the real edges and the self edge, and the skip
`h_dst @ W_skip` over the destination rows. Backward counts each of these
again for the weight (and, for the aggregation, the alpha) gradients,
and once more for input gradients except, at layer 0, the projection's
and the skip's (their input is the feature matrix). The head average,
softmax, LeakyReLU, ELU and other elementwise work are not counted.
"""
from __future__ import annotations

from chipbench.flops.sage import _layers


def _heads(cfg: dict, i: int, f_out: int):
    H = cfg["heads"]
    dh = f_out if i == cfg["num_layers"] - 1 else f_out // H
    return H, dh, H * dh


def step_flops(cfg: dict, counts: dict) -> float:
    total = 0.0
    for i, n_src, n_dst, e, f_in, f_out in _layers(cfg, counts):
        H, dh, hd = _heads(cfg, i, f_out)
        proj = 2.0 * n_src * f_in * hd
        scores = 2.0 * n_src * hd + 2 * 2.0 * n_dst * hd
        agg = 2.0 * e * hd + 2.0 * n_dst * hd
        skip = 2.0 * n_dst * f_in * f_out
        fwd = proj + scores + agg + skip
        total += fwd                      # forward
        total += fwd                      # weight and alpha gradients
        total += fwd - (proj + skip if i == 0 else 0.0)   # input gradients
    return total


def gather_agg_calls(cfg: dict, counts: dict) -> list:
    """Heads are folded into rows: each real edge is H kernel edges of
    width dh (128 and 47 at the published widths). Every layer runs the
    forward, the dw row dots (alpha has a gradient) and the dx scatter (z
    has one, for W's gradient, at layer 0 too)."""
    calls = []
    for i, n_src, n_dst, e, _, f_out in _layers(cfg, counts):
        H, dh, _ = _heads(cfg, i, f_out)
        for kind in ("fwd", "dw", "dx"):
            calls.append((kind, e * H, dh, n_dst * H, n_src * H))
    return calls
