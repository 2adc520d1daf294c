"""`correct` on the CPU at a tiny size: sound runs pass; a run with the
timed path broken underneath fails, once for each fault a one-chip
training cell can have, and once with caps too small for the batches; and the control (the reference computed in
bfloat16 in the program's place) fails the limits the chip cells use."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.layout import Layout
from chipbench.reference.common import compare
from repro.batching import calibrate
from repro.core import minibatch as mb
from repro.train import gnn_loop
from tiny import make_layout


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_layout(tmp_path_factory.mktemp("correct"))


def run_cell(root, workload, capsys, seed=12345):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.3", "--trace", "0"],
                  require_chip=False, root=root)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["sage-tiny-commrand", "gat-tiny-rand"])
def test_sound_run_is_correct(root, workload, capsys):
    result = run_cell(root, workload, capsys, seed=2 ** 32 + 3)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 16


def _state_kept(make_steps):
    def make(cfg, tcfg):
        step, ev = make_steps(cfg, tcfg)

        def kept(params, opt_state, *a):
            out = step(params, opt_state, *a)
            return (params, opt_state) + tuple(out[2:])
        return kept, ev
    return make


def _half_batch(make_steps):
    def make(cfg, tcfg):
        step, ev = make_steps(cfg, tcfg)

        def half(params, opt_state, batch, *a):
            n = batch.label_mask.shape[0]
            batch = dataclasses.replace(
                batch, label_mask=batch.label_mask & (jnp.arange(n) < n // 2))
            return step(params, opt_state, batch, *a)
        return half, ev
    return make


def _neighbor_altered(build):
    def altered(*a, **k):
        b = build(*a, **k)
        blk = b.blocks[0]
        n = b.levels[-1].shape[0]
        src = blk.src_pos.at[0, 0].set((blk.src_pos[0, 0] + 1) % n)
        return dataclasses.replace(
            b, blocks=[dataclasses.replace(blk, src_pos=src)] + b.blocks[1:])
    return altered


def _caps_shrunk(caps_for):
    def shrunk(*a, **k):
        # the deepest level's cap cut to a quarter, below the rows of the
        # level before it: the program's dedup drops the overflow without
        # a word, as caps calibrated too tight would
        caps = caps_for(*a, **k)
        return tuple(caps[:-1]) + (max(128, caps[-1] // 512 * 128),)
    return shrunk


FAULTS = {
    "caps_shrunk": (calibrate.CapsCalibrator, "caps_for", _caps_shrunk),
    "state_kept": (gnn_loop, "_make_steps", _state_kept),
    "half_batch": (gnn_loop, "_make_steps", _half_batch),
    "neighbor_altered": (mb, "_build_batch", _neighbor_altered),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(root, fault, monkeypatch, capsys):
    mod, name, wrap = FAULTS[fault]
    monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    result = run_cell(root, "sage-tiny-commrand", capsys)
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("workload", ["sage-tiny-commrand", "gat-tiny-rand"])
def test_bfloat16_control_is_not_correct(root, workload):
    layout = Layout(root)
    sess = run.Session(layout, workload, 7)
    sess.free_program()
    ref = sess.reference()
    nums = compare(sess.reference(dtype=jnp.bfloat16), ref, sess.params0)
    limits = layout.limits(workload)
    assert any(nums[k] > limits[k] for k in nums), nums
    sound = compare(sess.first, ref, sess.params0)
    assert all(sound[k] <= limits[k] for k in sound), sound
    jax.clear_caches()
