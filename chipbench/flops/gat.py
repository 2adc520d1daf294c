"""GAT operation counts from a batch's real counts (see `sage.py` for
`counts` and the layer/hop numbering).

Per layer with H heads of width dh (HD = H * dh): the projection
`z = h @ W` over the source rows, the per-source and per-destination
attention scores, the alpha-weighted aggregation over the real edges and
the self edge, and `W_out` where the heads do not fill the width.
Backward counts each of these again for the weight (and, for the
aggregation, the alpha) gradients, and once more for input gradients
except the projection's at layer 0. Softmax, LeakyReLU and other
elementwise work are not counted.
"""
from __future__ import annotations

from chipbench.flops.sage import _layers


def _heads(cfg: dict, f_out: int):
    H = cfg["heads"]
    dh = max(f_out // H, 1)
    return H, dh, H * dh


def step_flops(cfg: dict, counts: dict) -> float:
    total = 0.0
    for i, n_src, n_dst, e, f_in, f_out in _layers(cfg, counts):
        H, dh, hd = _heads(cfg, f_out)
        proj = 2.0 * n_src * f_in * hd
        scores = 2.0 * n_src * hd + 2 * 2.0 * n_dst * hd
        agg = 2.0 * e * hd + 2.0 * n_dst * hd
        out = 2.0 * n_dst * hd * f_out if hd != f_out else 0.0
        fwd = proj + scores + agg + out
        total += fwd                      # forward
        total += fwd                      # weight and alpha gradients
        total += fwd - (proj if i == 0 else 0.0)   # input gradients
    return total


def gather_agg_calls(cfg: dict, counts: dict) -> list:
    """Heads are folded into rows: each real edge is H kernel edges of
    width dh. Every layer runs the forward, the dw row dots (alpha has a
    gradient) and the dx scatter (z has one)."""
    calls = []
    for _, n_src, n_dst, e, _, f_out in _layers(cfg, counts):
        H, dh, _ = _heads(cfg, f_out)
        for kind in ("fwd", "dw", "dx"):
            calls.append((kind, e * H, dh, n_dst * H, n_src * H))
    return calls
