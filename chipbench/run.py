#!/usr/bin/env python3
"""Chip benchmark of GNN mini-batch training: one cell, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (an entry of `workloads` in BENCHMARK.json) names a configuration
and a traffic mix; `chipbench/layout.py` says where their files are.

A run:
1. loads the cell's graph (generated, prepared and cached under
   `chipbench/.cache/`), makes its features on the chip, and builds a
   `GNNTrainer` through the normal path (sync `BatchStream`,
   `agg_impl="auto"`, no feature cache, no checkpoints) with weights the
   benchmark makes from `--seed`;
2. calls `warmup()`, puts the stream's cursor 24 batches before the end
   of the first epoch (as a job resumed from a checkpoint does), then
   `train_steps(1)` and `train_steps(2)`, keeping the first gradient
   (read back from AdamW's first moment) and the parameters after three
   steps, then `train_steps(5)`; collects and freezes Python's garbage
   (set-up's objects stay out of the window's collections): set-up
   ends here;
3. drives `train_steps(16)` until `--seconds` have passed (each chunk
   ends in the trainer's own single sync). The first chunk ends the
   epoch, so every window holds an epoch boundary (the next epoch's
   order, the step without a batch dispatched ahead, the short last
   batch);
4. reads the device's peak memory, frees the trainer, and decides
   `correct`: the first three batches against the plain mirror of the
   policy and sampler, and the first three steps' losses, first gradient
   and parameter change against the plain float32 reference
   (`chipbench/reference/`), each number against its limit in
   `chipbench/limits/<cell>.json`.

`--trace 0` reports the cell's end-to-end metrics; `--trace 1` records
the window with the JAX profiler and reports its per-layer metrics, read
by `chipbench/metrics/<metric>.py`. The last line of standard output is
one JSON object; the numbers compared are also the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, the run exits 3 before any work and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench.layout import Layout  # noqa: E402

WARM_STEPS = 8          # untimed steps before the window (3 checked)
CHUNK = 16              # steps per `train_steps` call in the window
CHECKED = 3             # first steps the reference follows
COMPARED = ("loss_gap", "grad_gap", "update_gap", "batch_faults")
GAPS_NAMED = 100        # longest idle gaps named in the breakdown


def program_seed(seed: int) -> int:
    """The run's seed folded into [0, 2**31): JAX keys take 32 bits and
    the driver's seeds can be larger."""
    return int.from_bytes(hashlib.sha256(str(seed).encode()).digest()[:4],
                          "big") >> 1


def model_cfg(cfg: dict, spec) -> dict:
    """What the reference and the operation counts read of a config."""
    return {"model": cfg["model"], "num_layers": cfg["num_layers"],
            "hidden_dim": cfg["hidden_dim"], "in_dim": spec.feat_dim,
            "num_classes": spec.num_classes, "heads": cfg.get("heads", 1),
            "dropout": cfg["dropout"], "fanout": list(cfg["fanout"]),
            "learning_rate": cfg["learning_rate"],
            "weight_decay": cfg["weight_decay"]}


def host_batch(b) -> dict:
    """A program `MiniBatch` as host arrays in hop order (hop h maps
    level h to level h+1)."""
    import jax
    import numpy as np
    b = jax.device_get(b)
    return {"levels": [np.asarray(l, np.int64) for l in b.levels],
            "hops": [{"src_pos": np.asarray(k.src_pos, np.int64),
                      "self_pos": np.asarray(k.self_pos, np.int64),
                      "edge_mask": np.asarray(k.edge_mask),
                      "dst_mask": np.asarray(k.dst_mask)}
                     for k in b.blocks[::-1]],
            "labels": np.asarray(b.labels),
            "label_mask": np.asarray(b.label_mask)}


def batch_counts_fn(n_nodes: int):
    """Jitted per-batch counts: real nodes per level, real edges per hop
    (one small int32 vector; read after the window)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def counts(b):
        n = [jnp.sum(l < n_nodes) for l in b.levels]
        e = [jnp.sum(k.edge_mask) for k in b.blocks[::-1]]
        return jnp.stack(n + e).astype(jnp.int32)
    return counts


class Tap:
    """Sees every batch the trainer's stream hands out, without touching
    the program: the stream's `_take` is wrapped on the instance."""

    def __init__(self, stream, sink):
        self.stream, self.sink = stream, sink

    def __enter__(self):
        orig = self.stream._take

        def take(epoch, pos):
            b = orig(epoch, pos)
            self.sink(b)
            return b
        self.stream._take = take
        return self

    def __exit__(self, *exc):
        del self.stream._take


class GcClock:
    """How many garbage collections ran, and for how long, while it is
    entered (a `gc.callbacks` hook)."""

    def __init__(self):
        self.n, self.seconds, self._t = 0, 0.0, None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.seconds += time.perf_counter() - self._t


class Session:
    """One cell's trainer, set up and driven through its first steps."""

    def __init__(self, layout: Layout, workload: str, seed: int,
                 graph=None):
        import jax
        import numpy as np
        from repro.batching import CapsCalibrator, Cursor, make_policy
        from repro.configs.base import GNNConfig, TrainConfig
        from repro.optim import adamw
        from repro.train.gnn_loop import GNNTrainer

        from chipbench import dataset, graphgen
        from chipbench.reference.common import B1

        self.phases = {}
        t = time.perf_counter()

        def phase(name):
            nonlocal t
            now = time.perf_counter()
            self.phases[name] = now - t
            t = now

        self.layout = layout
        self.cell = layout.workload(workload)
        cfg = self.cfg = layout.config(self.cell["config"])
        self.spec = layout.graph_spec(cfg["graph"])
        self.traffic = layout.traffic(self.cell["traffic"])
        self.seed = program_seed(seed)
        cache = layout.dir / ".cache"
        if graph is None:
            graph = dataset.prepared_graph(self.spec, cache)
            graph.features = graphgen.features(self.spec, graph.labels,
                                               graph.communities)
        self.graph = graph
        phase("graph")
        self.mcfg = model_cfg(cfg, self.spec)
        knobs = {k: self.traffic[k] for k in ("mix", "p")
                 if k in self.traffic}
        policy = make_policy(self.traffic["policy"], **knobs)
        self.p = policy.p
        self.fanouts = tuple(cfg["fanout"])
        self.batch_size = cfg["batch_size"]
        gcfg = GNNConfig(
            name=self.cell["config"], model=cfg["model"],
            num_layers=cfg["num_layers"], hidden_dim=cfg["hidden_dim"],
            in_dim=self.spec.feat_dim, num_classes=self.spec.num_classes,
            fanout=self.fanouts, gat_heads=cfg.get("heads", 4),
            dropout=cfg["dropout"], dtype=cfg["dtype"], agg_impl="auto")
        tcfg = TrainConfig(batch_size=self.batch_size,
                           learning_rate=cfg["learning_rate"],
                           weight_decay=cfg["weight_decay"])
        # caps are a property of (graph, policy), calibrated once per
        # checkout; the trainer never evaluates, so eval shares them. A
        # mix whose batch sizes swing (COMM-RAND: a batch that straddles
        # two super-blocks holds twice the rows) probes whole epochs,
        # where the calibrator's few default probes would leave them out
        probe = {}
        if "caps_probe_epochs" in self.traffic:
            nb = -(-len(graph.train_ids) // self.batch_size)
            probe["n_probe"] = self.traffic["caps_probe_epochs"] * nb
        self.caps = CapsCalibrator(cache_path=str(cache / "caps.json"),
                                   seed=0, **probe).caps_for(
            graph, policy, self.batch_size, self.fanouts)
        phase("caps")
        tr = self.trainer = GNNTrainer(
            graph, gcfg, tcfg, policy, caps=self.caps, eval_caps=self.caps,
            seed=self.seed)
        self.ref_model = layout.module("reference", cfg["model"])
        params0 = self.ref_model.init(self.mcfg, jax.random.key(self.seed))
        tr.params, tr.opt_state = params0, adamw.init(params0)
        self.params0 = jax.device_get(params0)
        phase("trainer")
        tr.warmup()
        phase("warmup")
        # the window's first chunk ends epoch 0 (the tiny test graphs
        # have shorter epochs than that and start at 0)
        nb = tr.stream.num_batches(0)
        self.first_pos = max(0, nb - WARM_STEPS - CHUNK)
        tr.stream.cursor = Cursor(0, self.first_pos)

        batches = []
        with Tap(tr.stream, batches.append):
            losses = tr.train_steps(1)
            grad1 = jax.tree.map(lambda m: np.asarray(m) / (1 - B1),
                                 jax.device_get(tr.opt_state["m"]))
            losses += tr.train_steps(CHECKED - 1)
            params3 = jax.device_get(tr.params)
        self.first = {"losses": losses, "grad1": grad1, "params3": params3}
        self.counts_fn = batch_counts_fn(graph.num_nodes)
        self.counts_fn(batches[0])            # compiled outside the window
        self.batches = [host_batch(b) for b in batches[:CHECKED]]
        del batches
        tr.train_steps(WARM_STEPS - CHECKED)
        phase("first_steps")
        gc.collect()
        gc.freeze()
        phase("gc")

    def real_roots(self, epoch: int, pos: int, steps: int) -> int:
        """Real (non-padding) roots of `steps` batches from (epoch, pos):
        every batch is full but an epoch's last."""
        n = len(self.graph.train_ids)
        nb = -(-n // self.batch_size)
        total = 0
        for _ in range(steps):
            total += min(self.batch_size, n - pos * self.batch_size)
            pos += 1
            if pos == nb:
                pos = 0
        return total

    def window(self, seconds: float, sink=None):
        """Train in `CHUNK`-step calls until `seconds` have passed.
        Returns (steps, seconds taken, non-finite losses)."""
        import jax
        tr = self.trainer
        c = tr.stream.cursor
        epoch, pos = c.epoch, c.pos
        steps, bad = 0, 0
        with GcClock() as self.window_gc, \
                jax.profiler.TraceAnnotation("chipbench.window"):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("chipbench.train_steps"):
                    if sink is None:
                        losses = tr.train_steps(CHUNK)
                    else:
                        with Tap(tr.stream, sink):
                            losses = tr.train_steps(CHUNK)
                steps += CHUNK
                bad += sum(not math.isfinite(l) for l in losses)
                if time.perf_counter() - t0 >= seconds:
                    break
            dt = time.perf_counter() - t0
        self.window_roots = self.real_roots(epoch, pos, steps)
        return steps, dt, bad

    def free_program(self) -> None:
        """Drop the trainer and everything it put on the device (the
        graph's features are the benchmark's and stay)."""
        self.trainer = None
        gc.collect()

    def reference(self, dtype=None, half_batch: bool = False) -> dict:
        """The reference's first steps over the mirrored batches (the
        control: another `dtype`; a fault: `half_batch`)."""
        import jax
        import jax.numpy as jnp
        from chipbench.reference import batch as ref_batch
        from chipbench.reference import common
        dbs, dkeys, alt = [], [], 0
        for t in range(CHECKED):
            roots, key = self.roots_and_key(t)
            b = ref_batch.build(self.graph, roots, key, self.fanouts,
                                self.caps, self.p, follow=self.batches[t])
            alt += b["alt_taken"]
            dbs.append(common.device_batch(b))
            dkeys.append(jax.random.fold_in(key, 1))
        out = common.first_steps(self.ref_model, self.mcfg, self.params0,
                                 dbs, self.graph.features, dkeys,
                                 dtype=dtype or jnp.float32,
                                 half_batch=half_batch)
        out["alt_taken"] = alt
        return out

    def roots_and_key(self, t: int):
        import jax
        from chipbench.reference import order
        if not hasattr(self, "_order"):
            self._order = order.epoch_order(
                self.traffic, self.graph.train_ids, self.graph.communities,
                self.seed, 0)
        pos = self.first_pos + t
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(self.seed), 0), pos)
        return order.root_batch(self._order, pos, self.batch_size), key

    def batch_faults(self, batches=None) -> int:
        from chipbench.reference import batch as ref_batch
        bad = 0
        for t, got in enumerate(batches or self.batches):
            roots, key = self.roots_and_key(t)
            bad += ref_batch.check(self.graph, roots, key, self.fanouts,
                                   self.caps, self.p, got)
        return bad

    def compared(self, ref=None) -> dict:
        """The numbers `correct` is decided on: the program's first steps
        against `ref` (default: the reference's), and its batches."""
        from chipbench.reference.common import compare
        nums = compare(self.first, ref or self.reference(), self.params0)
        nums["batch_faults"] = self.batch_faults()
        return nums


def device_info() -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_peak_bytes():
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    return max(peaks) if all(p is not None for p in peaks) else None


def per_layer(sess: Session, trace_dir: Path, counts, steps: int,
              window_s: float, peaks: dict) -> tuple:
    """Per-layer metrics, device busy/window and the breakdown, from the
    window's trace and the batches' counts."""
    import bisect
    from jax.profiler import ProfileData
    from chipbench import trace_reduce as tr

    pd = ProfileData.from_file(tr.find_xplane(str(trace_dir)))
    host = tr.host_events(pd)
    lo, hi = tr.window(host, "chipbench.window")
    devs = [d for d in tr.devices(pd) if tr.busy_ns(d.ops, lo, hi) > 0]
    if not devs:
        raise RuntimeError("no device operation ran in the traced window")
    busy = sum(tr.busy_ns(d.ops, lo, hi) for d in devs) / len(devs)
    L = len(sess.fanouts)
    ctx = SimpleNamespace(
        layout=sess.layout, cfg=sess.mcfg, steps=steps, window_s=window_s,
        window_ns=hi - lo, busy_ns=busy, peaks=peaks,
        flops=sess.layout.module("flops", sess.cfg["model"]),
        counts=[{"n": [int(x) for x in c[:L + 1]],
                 "e": [int(x) for x in c[L + 1:]]} for c in counts],
        module_ns=lambda pat: sum(
            tr.matching_ns(d.modules, lo, hi, (pat,)) for d in devs)
        / len(devs),
        kernel_ns=lambda pats: sum(
            tr.matching_ns(d.ops, lo, hi, pats) for d in devs) / len(devs))
    metrics = {}
    for m in sess.layout.metrics("per_layer", sess.cell["name"]):
        v = sess.layout.module("metrics", m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    d0 = devs[0]
    mods = sorted((e.start, e.end, tr.module_name(e.name))
                  for e in d0.modules)
    starts = [m[0] for m in mods]

    def op_key(e):
        i = bisect.bisect_right(starts, e.start) - 1
        mod = mods[i][2] if i >= 0 and e.start < mods[i][1] else "?"
        return f"{mod}/{e.name}"
    op_ns = {}
    for e in d0.ops:
        d = min(e.end, hi) - max(e.start, lo)
        if d > 0:
            k = op_key(e)
            op_ns[k] = op_ns.get(k, 0.0) + d
    # idle time by what the host was doing: the longest gaps are named by
    # the innermost host span over their midpoint, the rest lumped
    gaps = {}
    for i, (s, e) in enumerate(sorted(tr.idle_gaps(d0.ops, lo, hi),
                                      key=lambda g: g[0] - g[1])):
        k = tr.host_context(host, (s + e) / 2) if i < GAPS_NAMED \
            else "shorter gaps"
        gaps[k] = gaps.get(k, 0.0) + (e - s)
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    breakdown = {"device_ops": top(op_ns), "idle_gaps": top(gaps)}
    return metrics, busy / 1e9, (hi - lo) / 1e9, breakdown


def run_cell(layout: Layout, workload: str, seed: int, seconds: float,
             trace: bool, trace_dir: Path = None) -> dict:
    import jax
    sess = Session(layout, workload, seed)
    dev = device_info()
    peaks = layout.peaks(dev["kind"]) if trace else None
    counts = []
    keep_trace = trace_dir is not None
    if trace:
        trace_dir = trace_dir or layout.dir / ".cache" / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    print("chipbench: set-up " + " ".join(
        f"{k} {v:.3f}s" for k, v in sess.phases.items()) +
        f" total {setup_s:.3f}s", file=sys.stderr, flush=True)
    steps, window_s, bad = sess.window(
        seconds, (lambda b: counts.append(sess.counts_fn(b)))
        if trace else None)
    if trace:
        jax.profiler.stop_trace()
    print(f"chipbench: window {steps} steps in {window_s:.3f}s from batch "
          f"{sess.first_pos} of {sess.trainer.stream.num_batches(0)}; "
          f"{sess.window_gc.n} garbage collections, "
          f"{sess.window_gc.seconds:.6f}s", file=sys.stderr, flush=True)
    dev["memory_peak_bytes"] = memory_peak_bytes()

    result = {"correct": None, "attempted": steps, "failed": bad}
    if trace:
        counts = jax.device_get(counts)
        metrics, busy_s, traced_s, breakdown = per_layer(
            sess, trace_dir, counts, steps, window_s, peaks)
        dev.update(busy_s=busy_s, window_s=traced_s)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"train_nodes_per_s": sess.window_roots / window_s,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in layout.metrics("end_to_end", workload)}
    result["metrics"] = metrics
    result["device"] = dev
    if trace:
        result["breakdown"] = breakdown

    sess.free_program()
    limits = layout.limits(workload)
    nums = sess.compared()
    compared = {k: {"value": nums[k], "limit": limits[k]} for k in COMPARED}
    result["correct"] = bad == 0 and all(
        v["value"] <= v["limit"] for v in compared.values())
    result["compared"] = compared
    return result


def main(argv=None, require_chip: bool = True, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the --trace 1 profile here (default: a "
                         "cache directory, deleted after reading)")
    args = ap.parse_args(argv)
    layout = Layout(root)
    cell = layout.workload(args.workload)

    import jax
    dev = device_info()
    if require_chip and (dev["platform"] != "tpu" or
                         dev["count"] < cell["chips"]):
        print(f"chipbench: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {dev['count']} {dev['platform']} "
              f"device(s)", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir",
                      str(layout.dir / ".cache" / "jax"))
    result = run_cell(layout, args.workload, args.seed, args.seconds,
                      bool(args.trace),
                      Path(args.trace_dir) if args.trace_dir else None)
    for k, v in result["compared"].items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # the compile cache lives in the checkout (its path is part of the
    # cache key); set before JAX reads its configuration
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        ROOT / "chipbench" / ".cache" / "jax")
    sys.exit(main())
