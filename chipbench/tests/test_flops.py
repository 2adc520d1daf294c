"""Operation and byte counts of `flops/` and `rooflines/`, checked
against a two-layer batch counted by hand.

The batch: 2 real roots (level 0), 3 real nodes at level 1, 5 at level
2; hop 0 (level 0 <- 1) has 4 real edges, hop 1 (level 1 <- 2) has 7.
Widths: 4 input features, hidden 4, 3 classes; GAT with 2 heads. Layer 0
runs hop 1 (5 src, 3 dst, 7 edges), layer 1 runs hop 0 (3 src, 2 dst, 4
edges).
"""
import pytest

from chipbench.flops import gat, sage
from chipbench.rooflines import gather_agg

CFG = {"num_layers": 2, "in_dim": 4, "hidden_dim": 4, "num_classes": 3,
       "heads": 2}
COUNTS = {"n": [2, 3, 5], "e": [4, 7]}


def test_sage_flops():
    # layer 0 (4 -> 4): two matmuls 2*2*3*4*4 = 192, aggregation
    # 2*7*4 = 56; forward 248 + weight grads 192, no input grads = 440
    # layer 1 (4 -> 3): matmuls 2*2*2*4*3 = 96, aggregation 2*4*4 = 32;
    # forward 128 + weight grads 96 + input grads 128 = 352
    assert sage.step_flops(CFG, COUNTS) == 440 + 352


def test_sage_calls():
    assert sage.gather_agg_calls(CFG, COUNTS) == [
        ("fwd", 7, 4, 3, 5), ("fwd", 4, 4, 2, 3), ("dx", 4, 4, 2, 3)]


def test_gat_flops():
    # layer 0 (4 -> 4, 2 heads of 2, HD 4): projection 2*5*4*4 = 160,
    # scores 2*5*4 + 2*2*3*4 = 88, aggregation 2*7*4 + 2*3*4 = 80, no
    # W_out; forward 328, backward 328 + (328 - 160) = 824
    # layer 1 (4 -> 3, 2 heads of 1, HD 2): projection 2*3*4*2 = 48,
    # scores 2*3*2 + 2*2*2*2 = 28, aggregation 2*4*2 + 2*2*2 = 24,
    # W_out 2*2*2*3 = 24; forward 124, total 3 * 124 = 372
    assert gat.step_flops(CFG, COUNTS) == 824 + 372


def test_gat_calls():
    # heads folded into rows: layer 0 has 14 edges of width 2 over 6 dst
    # and 10 src rows; layer 1 has 8 edges of width 1, 4 dst, 6 src rows
    l0 = [(k, 14, 2, 6, 10) for k in ("fwd", "dw", "dx")]
    l1 = [(k, 8, 1, 4, 6) for k in ("fwd", "dw", "dx")]
    assert gat.gather_agg_calls(CFG, COUNTS) == l0 + l1


def test_gather_agg_bytes():
    # fwd over 7 edges of width 4 into 3 rows: 2*7*4 = 56 operations;
    # 4 bytes * (7*4 rows + 2*7 index/weight + 3*4 out) = 216
    assert gather_agg.flops_bytes("fwd", 7, 4, 3, 5) == (56, 216)
    # dw: the same reads, cotangent rows in, weights out
    assert gather_agg.flops_bytes("dw", 7, 4, 3, 5) == (56, 216)
    # dx: 4 * (3*4 cotangent rows + 2*7 + 5*4 dx rows) = 184
    assert gather_agg.flops_bytes("dx", 7, 4, 3, 5) == (56, 184)
    with pytest.raises(ValueError):
        gather_agg.flops_bytes("other", 1, 1, 1, 1)


def test_least_time():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 1000.0}
    # fwd: max(56/100, 216/1000) = 0.56; dx: max(0.56, 0.184) = 0.56
    assert gather_agg.least_time_s(
        [("fwd", 7, 4, 3, 5), ("dx", 7, 4, 3, 5)], peaks) == \
        pytest.approx(1.12)
    peaks = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1000.0}
    # bandwidth-bound: 216/1000 + 184/1000
    assert gather_agg.least_time_s(
        [("fwd", 7, 4, 3, 5), ("dx", 7, 4, 3, 5)], peaks) == \
        pytest.approx(0.4)
