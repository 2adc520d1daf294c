"""The `gat-products` configuration's model, `gat_res`, on the CPU at a
tiny size: a sound run is correct; a run whose timed path drops the skip,
or concatenates the output heads where it should average them, is not;
the bfloat16 control fails the chip cell's limits; the operation counts
match a hand count; and the attention metric's scope patterns find the
program's score and softmax ops, forward and backward."""
import json
import re

import jax
import jax.numpy as jnp
import pytest

from chipbench import run, scopes
from chipbench.flops import gat_res
from chipbench.layout import Layout
from chipbench.reference.common import compare
from repro.models.gnn import models
from tiny import REPO, make_layout, tiny_config, write_json

CELL = "gatres-tiny-commrand"
CHIP_CELL = "gat-products-commrand"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_layout(tmp_path_factory.mktemp("gat_res"))
    cb = root / "chipbench"
    write_json(cb / "configs" / "gatres-tiny.json",
               {**tiny_config("sage"), "model": "gat_res", "heads": 4,
                "weight_decay": 0.0})
    write_json(cb / "limits" / f"{CELL}.json", json.loads(
        (REPO / "chipbench" / "limits" / f"{CHIP_CELL}.json").read_text()))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": CELL, "config": "gatres-tiny",
                               "traffic": "commrand", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        if CHIP_CELL in m["workloads"]:
            m["workloads"].append(CELL)
    write_json(root / "BENCHMARK.json", bench)
    return root


def run_cell(root, capsys, seed):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.3", "--trace", "0"], require_chip=False, root=root)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(root, capsys):
    result = run_cell(root, capsys, 2 ** 33 + 5)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 16


def _skip_dropped(gat_layer):
    def layer(p, *a, **k):
        return gat_layer({n: v for n, v in p.items()
                          if n not in ("w_skip", "b_skip")}, *a, **k)
    return layer


def _heads_concatenated(merge_heads):
    def merge(out, b):
        # heads concatenated as in the hidden layers; the last layer's
        # width then read from the first columns (its first head)
        n, H, dh = out.shape
        return out.reshape(n, H * dh)[:, :b.shape[-1]]
    return merge


FAULTS = {"skip_dropped": ("gat_layer", _skip_dropped),
          "heads_concatenated": ("_merge_heads", _heads_concatenated)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_model_is_not_correct(root, fault, monkeypatch, capsys):
    name, wrap = FAULTS[fault]
    monkeypatch.setattr(models, name, wrap(getattr(models, name)))
    result = run_cell(root, capsys, 12345)
    assert result["correct"] is False, result["compared"]


def test_bfloat16_control_is_not_correct(root):
    layout = Layout(root)
    sess = run.Session(layout, CELL, 7)
    sess.free_program()
    ref = sess.reference()
    nums = compare(sess.reference(dtype=jnp.bfloat16), ref, sess.params0)
    limits = layout.limits(CELL)
    assert any(nums[k] > limits[k] for k in nums), nums
    sound = compare(sess.first, ref, sess.params0)
    assert all(sound[k] <= limits[k] for k in sound), sound
    jax.clear_caches()


# two layers, as in test_flops.py: 2 real roots, 3 nodes at level 1, 5 at
# level 2; hop 0 has 4 real edges, hop 1 has 7. Widths: 4 features,
# hidden 4 (2 heads of 2, concatenated), 3 classes (2 heads of 3,
# averaged)
CFG = {"num_layers": 2, "in_dim": 4, "hidden_dim": 4, "num_classes": 3,
       "heads": 2}
COUNTS = {"n": [2, 3, 5], "e": [4, 7]}


def test_flops_by_hand():
    # layer 0 (5 src, 3 dst, 7 edges; 4 -> 4, HD 4): projection
    # 2*5*4*4 = 160, scores 2*5*4 + 2*2*3*4 = 88, aggregation
    # 2*7*4 + 2*3*4 = 80, skip 2*3*4*4 = 96; forward 424, weight grads
    # 424, input grads 424 - 160 - 96 = 168: 1016
    # layer 1 (3 src, 2 dst, 4 edges; 4 -> 3, HD 6): projection
    # 2*3*4*6 = 144, scores 2*3*6 + 2*2*2*6 = 84, aggregation
    # 2*4*6 + 2*2*6 = 72, skip 2*2*4*3 = 48; forward 348, total 3 * 348
    assert gat_res.step_flops(CFG, COUNTS) == 1016 + 1044


def test_gather_agg_calls_by_hand():
    # heads folded into rows: layer 0 has 14 edges of width 2 over 6 dst
    # and 10 src rows; layer 1 has 8 edges of width 3 (the classes) over
    # 4 dst and 6 src rows
    l0 = [(k, 14, 2, 6, 10) for k in ("fwd", "dw", "dx")]
    l1 = [(k, 8, 3, 4, 6) for k in ("fwd", "dw", "dx")]
    assert gat_res.gather_agg_calls(CFG, COUNTS) == l0 + l1


def test_attention_scopes_match_forward_and_backward(root):
    """The op names of a compiled `gat_res` train step, as the device
    trace carries them: the metric's patterns match ops of every layer's
    scores and softmax, in the forward and in the backward, and nothing
    else."""
    layout = Layout(root)
    metric = layout.module("metrics", "gat_attn_ms_per_step")
    sess = run.Session(layout, CELL, 3)
    tr = sess.trainer
    lowered = tr.train_step.lower(
        tr.params, tr.opt_state, tr.stream._take(0, 0), tr._train_feats,
        tr.degrees, 1e-3, tr._dropout_key(), None, 1.0, tr._skips)
    names = set(re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text()))
    match = scopes.matcher(metric.SCOPES)
    hit = {n for n in names if match(n)}
    sess.free_program()
    for layer in range(3):
        for part in ("scores", "softmax"):
            scope = f"gat/l{layer}/{part}"
            assert any(f"/jvp({scope})/" in n for n in hit), scope
            assert any(f"/transpose(jvp({scope}))/" in n for n in hit), scope
    assert all(re.search(r"gat/l\d/(scores|softmax)", n) for n in hit)
