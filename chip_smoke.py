#!/usr/bin/env python3
"""Chip smoke test: COMM-RAND GraphSAGE training through `GNNTrainer` on a
TPU, at the defaults of `examples/train_gnn_commrand.py` (reddit-like,
COMM-RAND-MIX-12.5% with p = 1.0, 3 layers, hidden 256, fanout (10, 10,
10), batch 1024). Weights come from seed 0 and the graph from its own seed.

    python chip_smoke.py             # one chip: the phases below
    python chip_smoke.py --chips 4   # four chips: the sharded path only

One chip:
  train      20 steps with the sync stream, 20 with the async pipeline
             (buffer donation on), one eval pass; every loss finite and
             the last below the first.
  reference  `gather_agg` Pallas against jnp at the largest layer shapes
             and at F=602 (a multi-pass dx), `gather_cached` Pallas
             against `gather_cached_ref` (forward and VJP, a 20% cache),
             and the first training losses of agg_impl="pallas" against
             agg_impl="jnp" (same seed, same batches).
  checkpoint a trainer resumed from the sync run's `ckpt_dir` reproduces
             the next loss exactly.
Four chips (`--chips 4`): sharded steps on a 4-device mesh whose feature
shards sit on 4 distinct devices, `halo_gather` on the device against
`halo_gather_np`, and the sharded first-step loss against the single-chip
train step over the same per-replica sub-batches.

Without a TPU the script exits 2 before any work. A failed phase exits 1.
The last line of a passing run is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import tempfile
import time
import traceback

import jax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DATASET = "reddit-like"
POLICY = {"name": "comm_rand", "mix": 0.125, "p": 1.0}
LAYERS, HIDDEN, FANOUT, BATCH = 3, 256, 10, 1024
STEPS = 20
# pallas vs jnp: the first loss is computed before any update, so only
# the summation order differs; later steps add Adam's normalization of
# near-zero gradients on top
REF_STEPS, REF_RTOL_FIRST, REF_RTOL = 5, 1e-4, 5e-3
KERNEL_TOL = 1e-4          # Pallas vs jnp kernel outputs (f32 sums)
SHARDED_RTOL = 1e-4        # sharded vs single-chip first-step loss


def log(msg: str) -> None:
    print(msg, flush=True)


class Setup:
    """Graph, configs and calibrated caps shared by every phase."""

    def __init__(self, dataset=DATASET, layers=LAYERS, hidden=HIDDEN,
                 fanout=FANOUT, batch=BATCH):
        from repro.batching import CapsCalibrator, make_policy
        from repro.configs.base import GNNConfig, TrainConfig
        from repro.core.reorder import prepare
        from repro.graphs import synthetic

        t0 = time.perf_counter()
        self.graph = prepare(synthetic.load(dataset))
        self.policy = make_policy(POLICY["name"], mix=POLICY["mix"],
                                  p=POLICY["p"])
        self.cfg = GNNConfig(f"sage-{dataset}", "sage", layers, hidden,
                             self.graph.feat_dim, self.graph.num_classes,
                             fanout=(fanout,) * layers)
        self.tcfg = TrainConfig(batch_size=batch)
        self.calibrator = CapsCalibrator()
        self.caps = None
        self.eval_caps = None
        log(f"graph {self.graph.name}: {self.graph.num_nodes} nodes, "
            f"{self.graph.num_edges} edges, F={self.graph.feat_dim}, "
            f"{self.graph.num_classes} classes "
            f"({time.perf_counter() - t0:.1f} s)")

    def trainer(self, cfg=None, **kw):
        """A seed-0 `GNNTrainer`; the first one calibrates the caps, the
        rest reuse them."""
        from repro.train.gnn_loop import GNNTrainer
        tr = GNNTrainer(self.graph, cfg or self.cfg, self.tcfg, self.policy,
                        caps=self.caps, eval_caps=self.eval_caps, seed=0,
                        calibrator=self.calibrator, **kw)
        self.caps, self.eval_caps = tr.caps, tr.eval_caps
        return tr


def timed_steps(tr, n: int, tag: str) -> list:
    """n single steps, each timed to completion (the loss is pulled to
    the host and the new params are blocked on)."""
    losses = []
    for k in range(n):
        t0 = time.perf_counter()
        loss = tr.train_steps(1)[0]
        jax.block_until_ready(tr.params)
        dt = time.perf_counter() - t0
        losses.append(loss)
        log(f"{tag} step {k:2d} loss {loss:.6f} step_ms {dt * 1e3:.3f}")
    return losses


def check_trajectory(losses: list, tag: str) -> None:
    if not all(math.isfinite(l) for l in losses):
        raise AssertionError(f"{tag}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{tag}: loss did not decrease ({losses[0]} -> {losses[-1]})")


def phase_train(s: Setup, ckpt_dir: str, steps: int = STEPS) -> dict:
    t0 = time.perf_counter()
    tr = s.trainer(ckpt_dir=ckpt_dir, ckpt_every=steps)
    log(f"caps {tr.caps} eval_caps {tr.eval_caps} "
        f"(calibrated in {time.perf_counter() - t0:.1f} s)")
    t0 = time.perf_counter()
    tr.warmup()
    log(f"sync compile_s {time.perf_counter() - t0:.1f}")
    sync = timed_steps(tr, steps, "sync")
    check_trajectory(sync, "sync")
    # one step past the checkpoint written at step `steps`
    next_loss = tr.train_steps(1)[0]

    ta = s.trainer(pipeline="async")
    t0 = time.perf_counter()
    ta.warmup()
    log(f"async compile_s {time.perf_counter() - t0:.1f}")
    async_ = timed_steps(ta, steps, "async")
    check_trajectory(async_, "async")
    ta.stream.close()
    log(f"async losses == sync losses: {async_ == sync}")

    ev = tr.evaluate(s.graph.val_ids)
    log(f"eval val_loss {ev['loss']:.6f} val_acc {ev['acc']:.4f}")
    if not (math.isfinite(ev["loss"]) and 0.0 <= ev["acc"] <= 1.0):
        raise AssertionError(f"eval: {ev}")
    return {"sync": sync, "next_loss": next_loss, "trainer": tr}


def _max_rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))


@functools.partial(jax.jit, static_argnums=0)
def run_agg(impl, x, idx, w, cot):
    """`gather_agg` output and its VJP (dx, dw) at cotangent `cot`."""
    from repro.kernels.gather_agg.ops import gather_agg
    out, vjp = jax.vjp(lambda x, w: gather_agg(x, idx, w, impl=impl), x, w)
    return (out, *vjp(cot))


@functools.partial(jax.jit, static_argnums=0)
def run_cached(impl, cache, feats, pos, ids, cot):
    """`gather_cached` rows and their VJP (d_cache, d_feats), and the
    (hits, misses) counters."""
    from repro.kernels.gather_cached.ops import gather_cached
    out, vjp = jax.vjp(
        lambda c, x: gather_cached(c, x, pos, ids, impl=impl)[0],
        cache, feats)
    return (out, *vjp(cot)), gather_cached(cache, feats, pos, ids,
                                           impl=impl)[1:]


def phase_reference(s: Setup, sync_losses: list) -> None:
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.gather_agg.kernel import dx_block_rows

    # kernels at the trainer's two largest layer shapes: layer 0 reads the
    # global feature matrix, the next layer the hidden activations; then
    # Reddit's width (602), whose dx block takes several source-row passes
    n_feats = s.graph.num_nodes
    shapes = [(n_feats, s.caps[-2], s.graph.feat_dim),
              (s.caps[-2], s.caps[-3] if len(s.caps) > 2 else BATCH,
               s.cfg.hidden_dim),
              (s.caps[-1], s.caps[-2], 602)]
    for n_src, n_dst, f in shapes:
        ks = jax.random.split(jax.random.key(7), 4)
        x = jax.random.normal(ks[0], (n_src, f), jnp.float32)
        idx = jax.random.randint(ks[1], (n_dst, FANOUT), 0, n_src)
        w = jax.random.normal(ks[2], (n_dst, FANOUT), jnp.float32)
        cot = jax.random.normal(ks[3], (n_dst, f), jnp.float32)

        got, want = (run_agg(impl, x, idx, w, cot)
                     for impl in ("pallas", "jnp"))
        for name, a, b in zip(("out", "dx", "dw"), got, want):
            err = float(jnp.max(jnp.abs(a - b) /
                                jnp.maximum(jnp.abs(b), 1.0)))
            log(f"kernel n_src={n_src} n_dst={n_dst} F={f} {name} "
                f"max_rel_err {err:.3e}")
            if not np.isfinite(err) or err > KERNEL_TOL:
                raise AssertionError(f"kernel {name} disagrees: {err}")
        log(f"kernel n_src={n_src} F={f} dx passes "
            f"{-(-n_src // dx_block_rows(n_src, f))}")

    # layer-0 cached read: a 20% cache of exact row copies, the input
    # level's ids with hits, misses and padding (ids >= N)
    rng = np.random.default_rng(7)
    f, m = s.graph.feat_dim, s.caps[-1]
    feats = jnp.asarray(rng.normal(size=(n_feats, f)), jnp.float32)
    admitted = rng.choice(n_feats, n_feats // 5, replace=False)
    pos = np.full(n_feats, -1, np.int32)
    pos[admitted] = np.arange(admitted.size)
    pos, cache = jnp.asarray(pos), feats[admitted]
    ids = jnp.asarray(np.where(rng.random(m) < 0.05, n_feats,
                               rng.integers(0, n_feats, m)), jnp.int32)
    cot = jnp.asarray(rng.normal(size=(m, f)), jnp.float32)

    (got, counts), (want, _) = (run_cached(impl, cache, feats, pos, ids,
                                           cot)
                                for impl in ("pallas", "jnp"))
    log(f"cached M={m} N={n_feats} C={admitted.size} F={f}: "
        f"hits {int(counts[0])} misses {int(counts[1])}")
    if not (int(counts[0]) > 0 and int(counts[1]) > 0):
        raise AssertionError(f"cached check needs hits and misses: {counts}")
    same = bool(jnp.array_equal(got[0], want[0]))
    log(f"cached rows == gather_cached_ref: {same}")
    if not same:
        raise AssertionError("gather_cached rows disagree with the reference")
    for name, a, b in zip(("d_cache", "d_feats"), got[1:], want[1:]):
        err = float(jnp.max(jnp.abs(a - b) / jnp.maximum(jnp.abs(b), 1.0)))
        log(f"cached {name} max_rel_err {err:.3e}")
        if not np.isfinite(err) or err > KERNEL_TOL:
            raise AssertionError(f"gather_cached {name} disagrees: {err}")

    tj = s.trainer(dataclasses.replace(s.cfg, agg_impl="jnp")).warmup()
    ref = [tj.train_steps(1)[0] for _ in range(REF_STEPS)]
    got = sync_losses[:REF_STEPS]
    for k, (a, b) in enumerate(zip(got, ref)):
        err = _max_rel([a], [b])
        tol = REF_RTOL_FIRST if k == 0 else REF_RTOL
        log(f"reference step {k} pallas {a:.6f} jnp {b:.6f} "
            f"rel {err:.3e} (tol {tol:g})")
        if not err <= tol:
            raise AssertionError(f"pallas vs jnp loss at step {k}: {err}")


def phase_checkpoint(s: Setup, ckpt_dir: str, steps: int,
                     next_loss: float) -> None:
    tr = s.trainer(ckpt_dir=ckpt_dir)
    if tr.global_step != steps:
        raise AssertionError(f"resumed at step {tr.global_step}, "
                             f"expected {steps}")
    log(f"resumed at step {tr.global_step} "
        f"(cursor {tr.stream.cursor.state()})")
    loss = tr.train_steps(1)[0]
    log(f"checkpoint next loss {next_loss:.9f} resumed {loss:.9f}")
    if loss != next_loss:
        raise AssertionError(f"resumed loss {loss} != {next_loss}")


def phase_sharded(s: Setup, n: int = 4, steps: int = 5) -> None:
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import halo
    from repro.dist import gnn as dist_gnn

    mesh = dist_gnn.make_gnn_mesh(n)
    tr = s.trainer(mesh=mesh)
    devs = {sh.device for sh in tr._train_feats["local"].addressable_shards}
    log(f"mesh devices {[d.id for d in mesh.devices.flat]}; feature "
        f"shards on {sorted(d.id for d in devs)}")
    if len(devs) != n:
        raise AssertionError(f"feature shards on {len(devs)} devices")

    # sharded first-step loss vs the single-chip train step over the same
    # per-replica sub-batches (same seed: same params and dropout key)
    single = s.trainer()
    cur = tr.stream.cursor
    batch = tr.stream.build(tr.stream.root_batches(cur.epoch)[cur.pos],
                            cur.epoch, cur.pos)
    key = jax.random.fold_in(tr.stream.batch_key(cur.epoch, cur.pos), 1)
    dev0 = jax.devices()[0]
    num = den = 0.0
    for r in range(n):
        sub = jax.device_put(jax.tree.map(lambda x: x[r], batch), dev0)
        out = single.train_step(single.params, single.opt_state, sub,
                                single.feats, single.degrees, 0.0, key,
                                None, 1.0, single._skips)
        m = float(sub.label_mask.sum())
        num, den = num + float(out[2]) * m, den + m
    want = num / max(den, 1.0)
    t0 = time.perf_counter()
    tr.warmup()
    log(f"sharded compile_s {time.perf_counter() - t0:.1f}")
    losses = timed_steps(tr, steps, "sharded")
    err = _max_rel([losses[0]], [want])
    log(f"sharded first loss {losses[0]:.6f} single-chip {want:.6f} "
        f"rel {err:.3e} (tol {SHARDED_RTOL:g})")
    if not err <= SHARDED_RTOL:
        raise AssertionError(f"sharded vs single-chip loss: {err}")
    check_trajectory(losses, "sharded")

    # halo exchange on the device == the host mirror
    d, ns, f, k = n, 64, 128, 96
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(d, ns, f)).astype(np.float32)
    ids = rng.integers(0, ns * d + 6, size=(d, k))

    def fn(fl, il):
        out, drop = halo.halo_gather(fl[0], il[0], n_per_shard=ns, r_cap=k,
                                     halo=d // 2, axis=dist_gnn.AXIS)
        return out[None], drop[None]

    spec = P(dist_gnn.AXIS)
    put = lambda a: jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec))
    out, drop = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec),
        check_vma=False))(put(feats), put(ids))
    out_np, drop_np = halo.halo_gather_np(feats, ids, n_per_shard=ns,
                                          r_cap=k, halo=d // 2)
    same = (np.array_equal(np.asarray(out), out_np)
            and np.array_equal(np.asarray(drop), drop_np))
    log(f"halo_gather device == host mirror: {same}")
    if not same:
        raise AssertionError("halo_gather disagrees with halo_gather_np")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded path on a 4-chip mesh only")
    args = ap.parse_args()

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX found {devs[0].platform} devices only")
        return 2
    if len(devs) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} TPU devices, "
            f"found {len(devs)}")
        return 2
    log(f"device_kind {devs[0].device_kind}")
    log(f"device_count {len(devs)}")
    log(f"jax {jax.__version__}")

    from repro.kernels.gather_agg.ops import resolve_agg_impl
    from repro.kernels.gather_cached.ops import resolve_cache_impl
    from repro.runtime import use_compile_cache
    log(f"agg_impl {resolve_agg_impl('auto')}")
    log(f"cache_impl {resolve_cache_impl('auto')}")
    log(f"compile_cache {use_compile_cache()}")

    t_start = time.perf_counter()
    failed = []

    def phase(name, fn, *a):
        t0 = time.perf_counter()
        log(f"== phase {name}")
        try:
            out = fn(*a)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"== phase {name} FAILED")
            return None
        log(f"== phase {name} passed ({time.perf_counter() - t0:.1f} s)")
        return out

    s = phase("setup", Setup)
    if s is None:
        return 1
    if args.chips == 4:
        phase("sharded", phase_sharded, s, args.chips)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ck:
            res = phase("train", phase_train, s, ck)
            if res is not None:
                phase("reference", phase_reference, s, res["sync"])
                phase("checkpoint", phase_checkpoint, s, ck, STEPS,
                      res["next_loss"])
    log(f"total_s {time.perf_counter() - t_start:.1f}")
    if failed:
        log(f"FAILED phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
