"""GNN mini-batch training loop — mirrors the paper's methodology (§5):
AdamW(lr=1e-3, wd=5e-4), batch 1024, fanout 10 per hop, up to 100 epochs,
early stopping on val loss (patience 6), ReduceLROnPlateau (patience 3),
metrics: final val acc, per-epoch time, epochs-to-converge, total time, and
the Fig-6 working-set metric (mean unique input nodes / feature bytes).

Batch construction goes through `repro.batching` end to end: the trainer
consumes a `BatchStream` whose `Cursor(epoch, pos)` is saved in every
checkpoint, so interrupted GNN runs resume bit-exactly (the contract the LM
trainer has always had). Dropout keys derive from the same (seed, epoch,
pos) as the stream, and `fit()`'s scheduler state (lr, plateau/early-stop
counters, best-so-far weights) is checkpointed alongside the cursor, so a
resumed run replays the same training trajectory.

Feature cache: `cache=` (a `repro.featcache.CachePlan`, admission-policy
name, `DynamicCacheState`, or `"dynamic[:admission]"`) routes every
layer-0 feature read through the device-resident cache (`gather_cached`)
— a pure read-path optimization (loss trajectory is bit-identical) whose
measured hit rate lands in each `EpochMetrics` via a `HitRateMeter`,
turning the paper's §6.5 cache-locality claim into a number this trainer
reports. With DYNAMIC admission the cache is trainer-carried mutable
state: every TRAIN step folds the extended device counters into the CLOCK
reference bits / candidate frequencies (`dynamic.ref_updates`, inside the
jitted step, reassembled host-side so the (C, F) rows are never copied),
and at every epoch boundary — in `run_epoch` AND when `train_steps`
crosses epochs — `dynamic.refill` swaps cold slots for hot missed rows.
The evolving state is checkpointed alongside the weights (plus the
boundary bookkeeping in `extra`), so interrupted dynamic-cache runs
resume with a bit-identical loss trajectory AND cache state. Evaluation
reads through the cache but never feeds the counters.

Guarded execution (`repro.resilience`): the jitted train step checks the
loss and every grad leaf for finiteness ON DEVICE and applies no update
on a non-finite step (a `jnp.where` select — no extra host sync; with
`poison=1.0` the guard is a bit-exact no-op). A device-resident
consecutive-skip counter rides through the step; with
`GNNTrainer(guard=GuardConfig(...))` the trainer syncs it every
`check_every` steps (and always at flush/checkpoint boundaries), and
past `max_consecutive_skips` escalates: `resilient_step` restores the
newest VALID checkpoint (`restore_latest` falls back across corrupt
ones) and replays — bit-exact, because batches, dropout keys and cache
state are pure functions of the checkpointed cursor. Skips, rollbacks
and checkpoint fallbacks are metered in a
`train.monitor.ResilienceMeter`. The dynamic cache additionally passes a
residency integrity check at every refill; on failure the trainer drops
to the uncached gather and keeps training (cache rows are bit-copies, so
the loss trajectory is unaffected), surfacing the event through the
`HitRateMeter` trajectory and the resilience meter.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import featcache, sampling
from repro.dist import gnn as dist_gnn
from repro.featcache import dynamic as featcache_dynamic
from repro.featcache.dynamic import DynamicCacheState
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsHub
from repro.batching import (BatchStream, CapsCalibrator, Cursor, as_policy,
                            eval_batches, make_policy)
from repro.configs.base import GNNConfig, TrainConfig
from repro.core import minibatch as mb
from repro.graphs.csr import DeviceGraph, Graph
from repro.kernels.gather_cached.ops import cache_stats
from repro.models.gnn.models import apply_gnn, init_gnn
from repro.optim import adamw
from repro.optim.schedule import EarlyStopping, ReduceLROnPlateau
from repro.resilience import faults
from repro.resilience.guard import as_guard
from repro.train import checkpoint as ckpt
from repro.train.losses import accuracy, gnn_softmax_ce
from repro.train.monitor import (HitRateMeter, ResilienceMeter, StepFailure,
                                 StragglerMonitor, resilient_step)


@dataclass
class EpochMetrics:
    epoch: int
    train_loss: float
    val_loss: float
    val_acc: float
    epoch_time_s: float
    mean_unique_nodes: float
    cache_hit_rate: float = 0.0     # measured (repro.featcache); 0 = no cache
    cache_refills: int = 0          # dynamic-CLOCK rows admitted (churn)
    straggler_fraction: float = 0.0  # slow-step fraction of THIS epoch


@dataclass
class TrainResult:
    policy: str
    val_acc: float                  # at best epoch
    test_acc: float
    epochs_to_converge: int
    per_epoch_time_s: float
    total_time_s: float
    mean_unique_nodes: float
    feature_bytes_per_batch: float
    caps: tuple
    history: List[EpochMetrics] = field(default_factory=list)
    cache: str = ""                 # cache describe(), "" = uncached
    cache_hit_rate: float = 0.0     # measured over the whole run
    cache_refills: int = 0          # total dynamic-CLOCK churn of the run
    straggler_fraction: float = 0.0  # slow-step fraction of the whole run


def _batch_cache_stats(cache, batch: mb.MiniBatch):
    """Device (hits, misses) for this batch's layer-0 reads — the same
    counters `gather_cached` computes inside `apply_gnn`."""
    if cache is None:
        return jnp.int32(0), jnp.int32(0)
    return cache_stats(cache.pos, batch.node_ids, cache.pos.shape[0])


def _make_steps(cfg: GNNConfig, tcfg: TrainConfig):
    @functools.partial(jax.jit, static_argnames=())
    def train_step(params, opt_state, batch: mb.MiniBatch, feats, degrees,
                   lr, key, cache, poison, skips):
        def loss_fn(p):
            # no (cap_L, F) pre-gather: layer 0 reads feature rows straight
            # from the global matrix through the fused gather-agg path —
            # or, with a cache plan, through the two-level gather_cached
            logits = apply_gnn(cfg, p, batch, feats, degrees, train=True,
                               dropout_key=key, feats_global=True,
                               cache=cache)
            # `poison` is 1.0 in normal runs (multiplying by 1.0 is a
            # bitwise no-op in IEEE) and NaN when the `step_nonfinite`
            # chaos site is armed: loss AND every grad go non-finite, so
            # the guard below must catch it
            return gnn_softmax_ce(logits, batch.labels,
                                  batch.label_mask.astype(jnp.float32)) \
                * poison

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # guarded execution, folded into the step (zero extra host syncs):
        # a non-finite loss or any non-finite grad leaf means this batch
        # applies NO update — params/opt are kept via a where-select and
        # the device-resident consecutive-skip counter increments
        ok = jnp.isfinite(loss)
        for g in jax.tree.leaves(grads):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
        new_params, new_opt = adamw.update(
            grads, opt_state, params, lr=lr,
            weight_decay=tcfg.weight_decay)

        def keep(new, old):
            return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)

        new_params, new_opt = keep(new_params, params), \
            keep(new_opt, opt_state)
        skips = jnp.where(ok, jnp.int32(0), skips + jnp.int32(1))
        hits, misses = _batch_cache_stats(cache, batch)
        # dynamic CLOCK admission: fold this batch's reads into the
        # reference bits / candidate frequencies ON DEVICE; only the three
        # accumulator arrays come back (the (C, F) rows are never copied).
        # NOT gated on `ok`: a skipped batch still touched its rows, and
        # replayed reads after a rollback refold identically anyway.
        refs = (featcache_dynamic.ref_updates(cache, batch.node_ids)
                if isinstance(cache, DynamicCacheState) else None)
        return new_params, new_opt, loss, ok, skips, hits, misses, refs

    @jax.jit
    def eval_step(params, batch: mb.MiniBatch, feats, degrees, cache):
        logits = apply_gnn(cfg, params, batch, feats, degrees, train=False,
                           feats_global=True, cache=cache)
        m = batch.label_mask.astype(jnp.float32)
        return (gnn_softmax_ce(logits, batch.labels, m),
                accuracy(logits, batch.labels, m), m.sum())

    return train_step, eval_step


class GNNTrainer:
    """One (graph, model, policy) training run over a `BatchStream`."""

    def __init__(self, graph: Graph, cfg: GNNConfig, tcfg: TrainConfig,
                 policy, caps=None, eval_caps=None, seed: int = 0,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                 calibrator: Optional[CapsCalibrator] = None,
                 cache=None, cache_capacity: Optional[int] = None,
                 cache_frac: float = 0.2, pipeline: str = "sync",
                 guard=None, mesh=None):
        self.graph = graph
        self.cfg = cfg
        self.tcfg = tcfg
        self.policy = as_policy(policy)
        self.seed = seed
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.g = DeviceGraph.from_graph(graph)
        self.feats = jnp.asarray(graph.features)
        self.labels = jnp.asarray(graph.labels)
        self.degrees = self.g.degrees
        self.fanouts = tuple(cfg.fanout[:cfg.num_layers])
        # the policy binds its neighbor sampler (repro.sampling); caps are
        # calibrated — and disk-cached — per (policy, sampler) pair
        self.sampler = sampling.for_policy(self.policy)
        cal = calibrator or CapsCalibrator(seed=seed)
        self.caps = caps or cal.caps_for(
            graph, self.policy, tcfg.batch_size, self.fanouts)
        # eval always uses the uniform policy (identical across compared
        # policies) — calibrate once with p=0.5
        self.eval_policy = make_policy("rand")
        self.eval_sampler = sampling.for_policy(self.eval_policy)
        eval_cal = calibrator or CapsCalibrator(seed=seed + 1)
        self.eval_caps = eval_caps or eval_cal.caps_for(
            graph, self.eval_policy, tcfg.batch_size, self.fanouts)
        self.train_step, self.eval_step = _make_steps(cfg, tcfg)
        self.params = init_gnn(cfg, jax.random.key(seed))
        self.opt_state = adamw.init(self.params)
        # `cache` is a CachePlan / DynamicCacheState / admission name /
        # "dynamic[:admission]" (built here against THIS policy's access
        # distribution); it rides on the stream and every step gathers
        # layer-0 features through it
        self.cache = featcache.as_cache(
            cache, graph, capacity=cache_capacity, frac=cache_frac,
            policy=self.policy, batch_size=tcfg.batch_size,
            fanouts=self.fanouts, seed=seed)
        # one metrics registry for the whole run (repro.obs): the three
        # meters below mirror every mutation into it, and `hub.export()`
        # is the versioned runtime-metrics artifact of this trainer
        self.hub = MetricsHub()
        self.cache_meter = HitRateMeter(hub=self.hub)
        self._pending_stats = []      # device counters, synced per epoch
        # per-step dispatch-time outlier tracking (host wall clock only —
        # no sync; observed on every `_train_one` dispatch), surfaced as
        # `EpochMetrics.straggler_fraction` + the "straggler/*" hub series
        self.straggler = StragglerMonitor(hub=self.hub)
        # guarded execution (repro.resilience): None/False disables (the
        # in-jit guard still runs but is never synced or escalated),
        # True = GuardConfig() defaults, or an explicit GuardConfig
        self.guard = as_guard(guard)
        self.guard_meter = ResilienceMeter(hub=self.hub)
        self._skips = jnp.zeros((), jnp.int32)   # device skip counter
        self._skips_host = 0          # last synced value (guard checks)
        self._pending_ok = []         # (ok, step) device flags, per flush
        # pipeline="sync" is the classic BatchStream (host epoch order +
        # single-slot async dispatch); "async" swaps in the depth-2
        # background prefetcher over the fused on-device builder
        # (`repro.pipeline`) — same Cursor semantics, bit-exact batches
        if pipeline not in ("sync", "async"):
            raise ValueError(
                f"pipeline must be 'sync' or 'async', got {pipeline!r}")
        # mesh=None is the classic single-device path. A 1-D ("shard",)
        # Mesh switches on data-parallel training (repro.dist.gnn): the
        # feature matrix is community-partitioned across the mesh, the
        # stream deals each global root batch as per-replica slices, and
        # the jitted step runs under shard_map with psum'd grads. The
        # global epoch order, cursor and checkpoints are unchanged — a
        # 1-replica mesh is bit-identical to mesh=None.
        self.mesh = mesh
        self.splan = None
        self._hplan = None
        self._hplan_epoch = -1
        self._step_cache = {}           # HaloPlan -> jitted sharded step
        self._remitter = None           # per-replica trace re-emitter
        stream_kwargs = {}
        if mesh is not None:
            if pipeline != "sync":
                raise ValueError(
                    "mesh training requires pipeline='sync' (the async "
                    "prefetcher is single-device for now)")
            if isinstance(self.cache, DynamicCacheState):
                raise ValueError(
                    "mesh training supports a static CachePlan only; "
                    "dynamic CLOCK admission is single-device for now")
            d = mesh.shape[dist_gnn.AXIS]
            if tcfg.batch_size % d:
                raise ValueError(
                    f"batch_size {tcfg.batch_size} not divisible by the "
                    f"{d}-replica mesh")
            self.splan = dist_gnn.community_shard_plan(graph, d)
            stream_cls = dist_gnn.ShardedBatchStream
            stream_kwargs.update(mesh=mesh, plan=self.splan)
        elif pipeline == "async":
            from repro.pipeline import AsyncBatchStream
            stream_cls = AsyncBatchStream
            # watchdog restarts surface in THIS trainer's resilience meter
            stream_kwargs["meter"] = self.guard_meter
        else:
            stream_cls = BatchStream
        self.pipeline = pipeline
        self.stream = stream_cls(
            graph, self.policy, tcfg.batch_size, self.fanouts, self.caps,
            seed=seed, device_graph=self.g, labels=self.labels,
            cache=self.cache, **stream_kwargs)
        if mesh is not None:
            # model/opt state is replicated; features live sharded with
            # the replicated id->slot map riding alongside them
            self._train_feats = {
                "local": self.splan.shard_features(graph.features, mesh),
                "pos": self.splan.device_pos(mesh)}
            self.params = dist_gnn.replicate(self.params, mesh)
            self.opt_state = dist_gnn.replicate(self.opt_state, mesh)
            self._skips = dist_gnn.replicate(self._skips, mesh)
            if self.cache is not None:
                self._set_cache(dist_gnn.replicate(self.cache, mesh))
            self.train_step = self._sharded_train_step
        else:
            self._train_feats = self.feats
        # epoch whose boundary refill is still pending (dynamic cache);
        # travels in checkpoint `extra` so resume never double-refills
        self._cache_epoch = self.stream.cursor.epoch
        self.global_step = 0
        self._best_params = None      # best-val weights seen by fit()
        self._fit_state = None        # lr / plateau / early-stop counters
        if ckpt_dir:
            self._try_resume()

    # -- checkpoint/resume (cursor + fit state travel with the weights) -----
    def _state(self):
        best = self._best_params if self._best_params is not None \
            else self.params
        state = {"params": self.params, "opt": self.opt_state, "best": best}
        if isinstance(self.cache, DynamicCacheState):
            # the evolving CLOCK state is training state: rows, residency,
            # reference bits, accumulators and hand all resume bit-exactly
            state["cache"] = self.cache
        return state

    def save(self) -> None:
        if not self.ckpt_dir:
            return
        ckpt.save(self.ckpt_dir, self.global_step, self._state(),
                  extra={"cursor": self.stream.cursor.state(),
                         "fit": self._fit_state,
                         "cache_epoch": self._cache_epoch})

    def _on_corrupt_ckpt(self, step: int, err: Exception) -> None:
        """`restore_latest` fallback hook: meter each corrupt/partial
        checkpoint skipped on the way to the newest valid one."""
        self.guard_meter.note("ckpt_fallbacks", ckpt_step=step,
                              error=str(err))

    def _apply_restored(self, step: int, tree, extra) -> None:
        """Install a restored checkpoint as the live training state
        (shared by startup resume and guard rollback)."""
        self.params, self.opt_state = tree["params"], tree["opt"]
        self._best_params = tree["best"]
        self.global_step = step
        self.stream.cursor = Cursor.from_state(extra["cursor"])
        self._fit_state = extra.get("fit")
        if "cache" in tree:
            self._set_cache(tree["cache"])
        self._cache_epoch = int(extra.get("cache_epoch",
                                          self.stream.cursor.epoch))

    def _shardings(self):
        """Checkpoint-restore shardings: replicated-on-mesh leaves in
        mesh mode (so a sharded-run resume lands its state back on the
        mesh, not on one device), None otherwise."""
        if self.mesh is None:
            return None
        return dist_gnn.state_shardings(self._state(), self.mesh)

    def _try_resume(self) -> None:
        step, tree, extra = ckpt.restore_latest(
            self.ckpt_dir, self._state(), shardings=self._shardings(),
            on_corrupt=self._on_corrupt_ckpt)
        if step is None:
            return
        self._apply_restored(step, tree, extra)

    # -- sharded step (repro.dist.gnn) --------------------------------------
    def _sharded_step_for(self, epoch: int):
        """The jitted sharded train step for `epoch`. The halo exchange
        budget is re-planned at every epoch boundary from that epoch's
        root order; the compiled step is cached per `HaloPlan`, so
        epochs whose plans agree (the steady state — COMM-RAND's orders
        shuffle blocks, not community membership) reuse one executable
        and never retrace (the recompile-stability contract
        `analysis.jaxpr_audit.audit_sharded_step` gates)."""
        if self._hplan_epoch != epoch:
            self._hplan = dist_gnn.plan_halo(
                self.splan, self.graph, self.fanouts, self.caps[-1],
                self.stream.root_batches(epoch))
            self._hplan_epoch = epoch
            self._remitter = dist_gnn.ReplicaTraceEmitter(
                self.splan.n_shards, self._hplan, self.caps[-1],
                self.graph.feat_dim)
        step = self._step_cache.get(self._hplan)
        if step is None:
            step = self._step_cache[self._hplan] = \
                dist_gnn.make_sharded_steps(
                    self.cfg, self.tcfg, self.mesh, self.splan,
                    self._hplan)
        return step

    def _sharded_train_step(self, params, opt_state, batch, feats, degrees,
                            lr, key, cache, poison, skips):
        return self._sharded_step_for(self.stream.cursor.epoch)(
            params, opt_state, batch, feats, degrees, lr, key, cache,
            poison, skips)

    # -- batch building -----------------------------------------------------
    def _dropout_key(self):
        """Derived from the batch the stream just yielded (cursor already
        advanced), so resumed runs replay identical dropout masks."""
        return jax.random.fold_in(
            self.stream.batch_key(self.stream.cursor.epoch,
                                  self.stream.cursor.pos - 1), 1)

    def warmup(self):
        """Trigger all jit compilations without disturbing training state
        (so per-epoch timings measure steady-state throughput)."""
        # the step may donate params and opt state: compile it on copies
        params, opt_state = jax.tree.map(
            lambda x: jax.device_put(x, may_alias=False),
            (self.params, self.opt_state))
        roots = np.full(self.tcfg.batch_size, -1, np.int64)
        roots[:min(len(self.graph.train_ids), 8)] = \
            self.graph.train_ids[:8]
        if self.mesh is not None:
            # the sharded stream stacks per-replica sub-batches; going
            # through it compiles the same build the epoch will use
            b = self.stream.build(roots, self.stream.cursor.epoch, 0)
        else:
            b = mb.build_batch(jax.random.key(0), self.g,
                               jnp.asarray(roots, jnp.int32), self.labels,
                               self.fanouts, self.caps, self.sampler)
        self.train_step(params, opt_state, b, self._train_feats,
                        self.degrees, 0.0, jax.random.key(0), self.cache,
                        1.0, self._skips)
        be = mb.build_batch(jax.random.key(0), self.g,
                            jnp.asarray(roots, jnp.int32), self.labels,
                            self.fanouts, self.eval_caps,
                            self.eval_sampler)
        params, cache = self._eval_state()
        self.eval_step(params, be, self.feats, self.degrees, cache)
        return self

    def _set_cache(self, cache) -> None:
        """Replace the carried cache state (and keep the stream's view —
        the plumbing consumers read it back from — in sync)."""
        self.cache = cache
        self.stream.cache = cache

    def _train_one(self, batch: mb.MiniBatch, lr: float):
        t0 = time.perf_counter()
        step0 = self.global_step
        with obs_trace.span("train_step", cat="step", step=step0):
            poison = 1.0
            if faults.fire("step_nonfinite",
                           step=self.global_step) is not None:
                # chaos site: NaN the loss inside the jitted step — python
                # floats are weak-typed scalars, so 1.0 vs nan never
                # retraces
                poison = float("nan")
            self.params, self.opt_state, loss, ok, self._skips, hits, \
                misses, refs = self.train_step(
                    self.params, self.opt_state, batch, self._train_feats,
                    self.degrees, lr, self._dropout_key(), self.cache,
                    poison, self._skips)
            if self.cache is not None:
                # keep the device counters un-synced: a float()/int()
                # here would serialize away the stream's prefetch overlap
                self._pending_stats.append((hits, misses))
            if self.guard is not None:
                self._pending_ok.append((ok, self.global_step))
            if isinstance(refs, dict):
                # sharded step: the slot carries the per-replica aux
                # payload (loss share / halo drops / cache counters as
                # un-synced (D,) arrays), not dynamic-cache refs — queue
                # it for the per-replica trace re-emission at the epoch
                # boundary drain
                if self._remitter is not None and \
                        obs_trace.current() is not None:
                    self._remitter.note(
                        t0 * 1e6, (time.perf_counter() - t0) * 1e6,
                        step0, refs)
            elif refs is not None:
                self._set_cache(
                    featcache_dynamic.with_refs(self.cache, refs))
            self.global_step += 1
            # a checkpoint due at this step forces a guard sync first: we
            # must NEVER checkpoint mid-skip-burst, or a later rollback to
            # that checkpoint would permanently lose the skipped batches
            # (the replayed trajectory could not bit-match a clean run)
            # analysis: allow[no-host-sync-in-hot-path] -- bool() over host ints/paths (ckpt cadence), no device operand
            due_ckpt = bool(self.ckpt_dir and self.ckpt_every and
                            self.global_step % self.ckpt_every == 0)
            rolled = self._guard_check(force=due_ckpt)
            # refill BEFORE any checkpoint at this step: a boundary
            # checkpoint then carries the post-refill state + advanced
            # _cache_epoch, so a resumed run neither skips nor repeats
            # the refill
            self._maybe_refill()
            if due_ckpt and not rolled and self._skips_host == 0:
                self.save()
        # host dispatch time (never a device sync): a straggler here is a
        # slow HOST — batch starvation, dispatch overhead, rollback work
        self.straggler.observe(time.perf_counter() - t0, step0)
        return loss

    def _maybe_refill(self) -> None:
        """Epoch-boundary CLOCK eviction/refill (dynamic cache only).

        Called after every consumed batch, in `run_epoch` AND
        `train_steps`: the cursor reaching the end of epoch
        `_cache_epoch` triggers exactly one refill per boundary — the one
        point where residency may change, outside all differentiated
        code. Syncs one int (the churn) per epoch."""
        if not isinstance(self.cache, DynamicCacheState):
            return
        c = self.stream.cursor
        at_end = c.pos >= self.stream.num_batches(c.epoch)
        if not (c.epoch > self._cache_epoch or
                (c.epoch == self._cache_epoch and at_end)):
            return
        # cat="sync": the refill's churn count + integrity check round-trip
        # to host. It fires inside the epoch's LAST train step (so the
        # mid-epoch-sync gate sanctions it by construction).
        with obs_trace.span("cache_refill", cat="sync", epoch=c.epoch):
            self._refill_now(c, at_end)

    def _refill_now(self, c, at_end: bool) -> None:
        state, admitted = featcache_dynamic.refill(self.cache, self.feats)
        if not featcache_dynamic.integrity_ok(state):
            # graceful degradation: residency invariants broken (the
            # cache_corrupt chaos site, or a real bug) — drop to the
            # uncached feats_global gather rather than serve rows through
            # a corrupt pos map. Detected HERE, at the refill boundary
            # BEFORE any read goes through the new state, so every loss
            # ever computed came from intact bit-copies of the global
            # rows and the trajectory stays bit-identical to an
            # uncorrupted run.
            self.guard_meter.note("cache_degradations",
                                  step=self.global_step, epoch=c.epoch)
            self.cache_meter.note_degraded(self.global_step)
            self._pending_stats = []    # counters of the dropped state
            self._set_cache(None)
            return
        self._set_cache(state)
        self.cache_meter.observe_refill(admitted)
        self._cache_epoch = c.epoch + 1 if at_end else c.epoch

    def _flush_cache_stats(self) -> None:
        """Sync pending per-batch device flags: cache counters into the
        hit-rate meter, guard ok flags into the resilience meter."""
        if not (self._pending_stats or self._pending_ok):
            return
        with obs_trace.span("stats_flush", cat="sync",
                            n=(len(self._pending_stats) +
                               len(self._pending_ok))):
            for h, m in self._pending_stats:
                self.cache_meter.observe(h, m)
            self._pending_stats = []
            for ok, step in self._pending_ok:
                if not bool(ok):
                    self.guard_meter.note("skipped_steps", step=step)
            self._pending_ok = []

    # -- guarded execution (repro.resilience) -------------------------------
    def _guard_check(self, force: bool = False) -> bool:
        """Sync the device skip counter when due (`check_every` cadence,
        or forced at flush/checkpoint boundaries) and escalate past the
        consecutive-skip budget. Returns True if it rolled back."""
        g = self.guard
        if g is None:
            return False
        if not (force or (g.check_every > 0 and
                          self.global_step % g.check_every == 0)):
            return False
        with obs_trace.span("guard_sync", cat="sync",
                            step=self.global_step):
            # analysis: allow[no-host-sync-in-hot-path] -- THE one guard sync, amortized by check_every cadence (see GuardConfig)
            self._skips_host = int(self._skips)  # the one guard sync
        if self._skips_host <= g.max_consecutive_skips:
            return False
        self._escalate()
        return True

    def _escalate(self) -> None:
        """Consecutive-skip budget blown: roll back to the newest VALID
        checkpoint and replay. Replay is clean for transient causes
        (an armed fault window is behind the invocation counter by the
        time the replayed steps re-fire) and bit-exact because batches,
        dropout keys and cache state are pure functions of the restored
        cursor. Persistent causes re-escalate until `max_rollbacks`,
        then raise StepFailure."""
        self._flush_cache_stats()       # meter the skips we're erasing
        self.guard_meter.note("rollbacks", step=self.global_step,
                              skips=self._skips_host)
        if self.guard_meter.rollbacks > self.guard.max_rollbacks:
            raise StepFailure(
                f"non-finite steps persisted through "
                f"{self.guard.max_rollbacks} rollbacks "
                f"(step {self.global_step})")
        if not self.ckpt_dir:
            raise StepFailure(
                f"{self._skips_host} consecutive non-finite steps at step "
                f"{self.global_step} and no ckpt_dir to roll back to")

        def _restore():
            step, tree, extra = ckpt.restore_latest(
                self.ckpt_dir, self._state(), shardings=self._shardings(),
                on_corrupt=self._on_corrupt_ckpt)
            if step is None:
                raise StepFailure(
                    f"rollback found no valid checkpoint in "
                    f"{self.ckpt_dir}")
            return step, tree, extra

        with obs_trace.span("ckpt_rollback", cat="ckpt",
                            step=self.global_step,
                            skips=self._skips_host):
            (step, tree, extra), _ = resilient_step(
                _restore, max_retries=1, backoff_s=0.05)
            self._apply_restored(step, tree, extra)
        self._skips = jnp.zeros((), jnp.int32)
        self._skips_host = 0
        self._pending_stats = []
        self._pending_ok = []

    def run_epoch(self, lr: float) -> Dict:
        """Consume the remainder of the stream's current epoch (the
        epoch-boundary refill fires inside `_train_one` at the last
        batch, so the dynamic cache is already post-refill on return)."""
        t0 = time.perf_counter()
        e0 = self.stream.cursor.epoch
        mark = self.cache_meter.mark()
        smark = self.straggler.mark()
        losses, uniq = [], []
        # the epoch envelope span is what the trace analyzer's mid-epoch
        # sync gate anchors on: every cat="sync" span starting inside it
        # before the final train step fails `--forbid-mid-epoch-sync`
        with obs_trace.span("epoch", cat="loop", epoch=e0):
            for batch in self.stream.epoch():
                losses.append(self._train_one(batch, lr))
                uniq.append(batch.num_unique)
            if losses:
                with obs_trace.span("epoch_flush", cat="sync", epoch=e0,
                                    n_steps=len(losses)):
                    # analysis: allow[no-host-sync-in-hot-path] -- epoch-boundary flush: one drain per epoch so `time` covers real device work
                    jax.block_until_ready(losses[-1])
                if self._remitter is not None:
                    # per-replica Perfetto tracks, reconstructed from the
                    # queued aux (device already drained, so the host
                    # transfers here cost no new sync)
                    self._remitter.flush(obs_trace.current(), e0)
            dt = time.perf_counter() - t0
            self._flush_cache_stats()
            self._guard_check(force=True)  # epoch boundary: exact skips
        self.hub.mark_epoch(e0)
        if not losses:          # resumed exactly on an epoch boundary
            return {"loss": 0.0, "time": dt, "uniq": 0.0,
                    "cache_hit": 0.0, "cache_refill": 0,
                    "straggler": 0.0}
        ep = self.cache_meter.note_epoch(mark) if self.cache is not None \
            else {"hit_rate": 0.0, "refills": 0}
        # analysis: allow[no-host-sync-in-hot-path] -- post-flush metric reduction at the epoch boundary; device is already drained
        return {"loss": float(np.mean([float(l) for l in losses])),
                "time": dt,
                # a sharded batch carries (D,) per-replica unique counts;
                # np.asarray averages them (scalar-safe for mesh=None)
                # analysis: allow[no-host-sync-in-hot-path] -- post-flush metric reduction at the epoch boundary; device is already drained
                "uniq": float(np.mean([np.asarray(u).mean()
                                       for u in uniq])),
                "cache_hit": ep["hit_rate"],
                "cache_refill": ep["refills"],
                "straggler": self.straggler.fraction_since(smark)}

    def train_steps(self, n: int, lr: Optional[float] = None) -> List[float]:
        """Consume exactly `n` batches (crossing epoch boundaries)."""
        lr = self.tcfg.learning_rate if lr is None else lr
        it = iter(self.stream)
        # keep losses on device until the end: a float() per step would
        # sync every batch and serialize away the stream's prefetch overlap
        losses = [self._train_one(next(it), lr) for _ in range(n)]
        self._flush_cache_stats()
        self._guard_check(force=True)
        with obs_trace.span("steps_flush", cat="sync", n=n):
            # analysis: allow[no-host-sync-in-hot-path] -- single batched sync at the END of the n-step run (see comment above: no per-step float)
            out = [float(l) for l in losses]
        if self._remitter is not None:
            self._remitter.flush(obs_trace.current(),
                                 self.stream.cursor.epoch)
        return out

    def evaluate(self, ids: np.ndarray) -> Dict:
        with obs_trace.span("eval", cat="eval", n_ids=len(ids)):
            return self._evaluate(ids)

    def _eval_state(self):
        """(params, cache) for `eval_step`, which runs on the device that
        holds the whole feature matrix. In mesh mode the replicated state
        is copied there first: a jit over mesh-placed inputs would leave
        the partitioning to the compiler, and it cannot partition a
        Pallas (Mosaic) kernel."""
        state = (self.params, self.cache)
        if self.mesh is None:
            return state
        return jax.device_put(state, self.feats.sharding)

    def _evaluate(self, ids: np.ndarray) -> Dict:
        params, cache = self._eval_state()
        tot_l, tot_a, tot_n = 0.0, 0.0, 0.0
        for batch in eval_batches(
                self.graph, ids, self.tcfg.batch_size, self.fanouts,
                self.eval_caps, sampler=self.eval_sampler,
                seed=self.seed + 17,
                device_graph=self.g, labels=self.labels):
            l, a, n = self.eval_step(params, batch, self.feats,
                                     self.degrees, cache)
            # analysis: allow[no-host-sync-in-hot-path] -- evaluation accumulates on host; eval batches are not prefetch-overlapped
            n = float(n)
            # analysis: allow[no-host-sync-in-hot-path] -- evaluation accumulates on host; eval batches are not prefetch-overlapped
            tot_l += float(l) * n
            # analysis: allow[no-host-sync-in-hot-path] -- evaluation accumulates on host; eval batches are not prefetch-overlapped
            tot_a += float(a) * n
            tot_n += n
        return {"loss": tot_l / max(tot_n, 1), "acc": tot_a / max(tot_n, 1)}

    def fit(self, verbose: bool = False) -> TrainResult:
        stopper = EarlyStopping(self.tcfg.early_stop_patience)
        plateau = ReduceLROnPlateau(self.tcfg.learning_rate,
                                    self.tcfg.plateau_factor,
                                    self.tcfg.plateau_patience)
        history: List[EpochMetrics] = []
        best_val_acc = 0.0
        best_params = self._best_params if self._best_params is not None \
            else self.params
        lr = self.tcfg.learning_rate
        start_epoch = 0
        if self._fit_state:                   # resumed mid-training
            fs = self._fit_state
            lr, start_epoch = fs["lr"], fs["epoch"]
            best_val_acc = fs["best_val_acc"]
            plateau.lr, plateau.best, plateau.bad = fs["plateau"]
            stopper.best, stopper.bad, stopper.best_epoch = fs["stopper"]
        if stopper.bad >= stopper.patience:
            # checkpoint came from an ALREADY-FINISHED (early-stopped) run:
            # don't train further from best_params
            start_epoch = self.tcfg.max_epochs
        t_start = time.perf_counter()
        for epoch in range(start_epoch, self.tcfg.max_epochs):
            em = self.run_epoch(lr)
            ev = self.evaluate(self.graph.val_ids)
            history.append(EpochMetrics(epoch, em["loss"], ev["loss"],
                                        ev["acc"], em["time"], em["uniq"],
                                        em["cache_hit"],
                                        em["cache_refill"],
                                        em["straggler"]))
            if verbose:
                print(f"  epoch {epoch:3d} loss={em['loss']:.4f} "
                      f"val={ev['acc']:.4f} t={em['time']:.2f}s "
                      f"uniq={em['uniq']:.0f} "
                      f"cache_hit={em['cache_hit']:.3f} "
                      f"refill={em['cache_refill']}")
            if ev["acc"] > best_val_acc:
                best_val_acc = ev["acc"]
                best_params = jax.tree.map(lambda x: x, self.params)
            lr = plateau.step(ev["loss"])
            stop = stopper.update(ev["loss"], epoch)
            self._best_params = best_params
            self._fit_state = {
                "lr": lr, "epoch": epoch + 1, "best_val_acc": best_val_acc,
                "plateau": [plateau.lr, plateau.best, plateau.bad],
                "stopper": [stopper.best, stopper.bad, stopper.best_epoch],
            }
            if stop:
                break
        total = time.perf_counter() - t_start
        self.params = best_params
        if self.ckpt_dir:
            self.save()
        test = self.evaluate(self.graph.test_ids)
        n_epochs = len(history)

        def _mean(xs):                # empty when resuming a finished run
            return float(np.mean(xs)) if xs else 0.0

        return TrainResult(
            policy=self.policy.describe(),
            val_acc=best_val_acc,
            test_acc=test["acc"],
            epochs_to_converge=stopper.best_epoch + 1
            if stopper.best_epoch >= 0 else n_epochs,
            per_epoch_time_s=_mean([h.epoch_time_s for h in history]),
            total_time_s=total,
            mean_unique_nodes=_mean([h.mean_unique_nodes for h in history]),
            feature_bytes_per_batch=_mean([h.mean_unique_nodes
                                           for h in history])
            * self.graph.feat_dim * 4,
            caps=self.caps,
            history=history,
            cache=self.cache.describe() if self.cache is not None else "",
            cache_hit_rate=self.cache_meter.hit_rate,
            cache_refills=self.cache_meter.refills,
            straggler_fraction=self.straggler.straggler_fraction,
        )


def train_once(graph: Graph, cfg: GNNConfig, policy,
               tcfg: Optional[TrainConfig] = None, seed: int = 0,
               verbose: bool = False,
               calibrator: Optional[CapsCalibrator] = None,
               cache=None, pipeline: str = "sync",
               guard=None) -> TrainResult:
    tcfg = tcfg or TrainConfig()
    return GNNTrainer(graph, cfg, tcfg, policy, seed=seed,
                      calibrator=calibrator, cache=cache,
                      pipeline=pipeline, guard=guard).warmup().fit(verbose)
