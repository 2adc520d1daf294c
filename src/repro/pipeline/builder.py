"""`DeviceBatchBuilder`: fused on-device batch construction.

The synchronous `BatchStream` path does real host work per batch: slice a
numpy root array, ship it host->device, then dispatch the jitted
sample/dedup builder. This builder removes the per-batch host leg
entirely:

  * the EPOCH root order is computed on device (`device_order`) and stays
    resident for the whole epoch as one padded (num_batches * B,) buffer
    — exactly one order computation per epoch, zero per-batch transfers
    (the previous epoch's buffer is donated to the refresh off-CPU);
  * one fused jit derives the batch PRNG keys from (seed, epoch, pos),
    slices batch `pos`'s roots out of the resident order
    (`lax.dynamic_slice`), and runs the SAME `_build_batch_impl` body the
    stream uses — so the produced `MiniBatch` is bit-exact against
    `BatchStream.build` for the same cursor;
  * shared-randomness sampler state (LABOR's per-node ranks) is hoisted
    to one pass per EPOCH (`epoch_ranks`) and threaded into every build
    of that epoch.

Policies without a device order program fall back to the numpy
`epoch_order` once per epoch (still one transfer per epoch, not per
batch).

`stage_times` is the shared per-stage microbenchmark (roots prep /
neighbor sample / dedup+remap) used by `benchmarks/pipeline_bench.py` and
`benchmarks/sampler_bench.py`'s `build_breakdown_us` columns.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sampling
from repro.batching.policy import as_policy
from repro.core import minibatch as mb
from repro.graphs.csr import DeviceGraph, Graph
from repro.obs import trace as obs_trace
from repro.pipeline.device_order import (OrderSpec, device_epoch_order,
                                         epoch_words_for)
from repro.resilience import faults


@functools.partial(jax.jit, static_argnames=("P",))
def _pad_fresh(order, P: int):
    """(P,) int32 order buffer, -1 padded past the true order length."""
    return jnp.full((P,), -1, jnp.int32).at[:order.shape[0]].set(order)


@functools.partial(jax.jit, donate_argnums=(1,))
def _pad_into(order, scratch):
    """Same as `_pad_fresh` but recycles the previous epoch's buffer via
    donation — the refresh writes in place instead of allocating (used
    off-CPU only; CPU donation is a no-op that logs warnings)."""
    return scratch.at[:].set(-1).at[:order.shape[0]].set(order)


@functools.partial(jax.jit,
                   static_argnames=("B", "fanouts", "caps", "sampler"))
def _fused_build(seed_key, epoch, pos, g, order_pad, labels_all,
                 shared_ctx, B: int, fanouts, caps, sampler):
    """Key derivation + root slice + build, one dispatch. `epoch`/`pos`
    ride in as int32 scalars (traced, no retrace per batch); the keys are
    the stream's exact derivation — fold_in(fold_in(key(seed), epoch),
    pos) — computed on device."""
    ek = jax.random.fold_in(seed_key, epoch)
    bk = jax.random.fold_in(ek, pos)
    roots = jax.lax.dynamic_slice(order_pad, (pos * B,), (B,))
    return mb._build_batch_impl(bk, ek, g, roots, labels_all,
                                fanouts, caps, sampler, shared_ctx)


class DeviceBatchBuilder:
    """Per-(epoch, pos) `MiniBatch` factory with a device-resident epoch
    order. Mirrors `BatchStream`'s deterministic derivations exactly:
    `build(epoch, pos)` == `stream.build(root_batches(epoch)[pos], epoch,
    pos)` bit for bit."""

    def __init__(self, graph: Graph, policy, batch_size: int, fanouts,
                 caps, *, seed: int = 0, drop_last: bool = False,
                 sampler=None, mode: str = "sample",
                 device_graph: Optional[DeviceGraph] = None,
                 labels=None):
        self.graph = graph
        self.policy = as_policy(policy)
        self.batch_size = int(batch_size)
        self.fanouts = tuple(fanouts)
        self.caps = tuple(caps)
        self.seed = seed
        self.drop_last = drop_last
        self.sampler = sampling.resolve(
            sampler, mode, lambda: sampling.for_policy(self.policy))
        self.g = device_graph or DeviceGraph.from_graph(graph)
        self.labels = labels if labels is not None \
            else jnp.asarray(graph.labels)
        T = len(graph.train_ids)
        self.num_batches = T // self.batch_size if drop_last \
            else -(-T // self.batch_size)
        self.padded_len = self.num_batches * self.batch_size
        try:
            self.spec = OrderSpec.for_policy(graph, self.policy)
        except NotImplementedError:
            self.spec = None            # host numpy order, once per epoch
        # donation recycles the order buffer only off-CPU (CPU donation
        # is rejected by XLA and logs a warning per dispatch)
        self._donate = jax.default_backend() != "cpu"
        self._seed_key = jax.random.key(seed)
        self._order_cache = (-1, None)
        self._ranks_cache = (-1, None)

    @classmethod
    def from_stream(cls, stream) -> "DeviceBatchBuilder":
        """A builder sharing a `BatchStream`'s graph/sampler/derivations
        (same device graph + labels arrays — no duplicate residency)."""
        return cls(stream.graph, stream.policy, stream.batch_size,
                   stream.fanouts, stream.caps, seed=stream.seed,
                   drop_last=stream.drop_last, sampler=stream.sampler,
                   device_graph=stream.g, labels=stream.labels)

    # -- deterministic derivations (identical to BatchStream) ---------------
    def epoch_key(self, epoch: int):
        return jax.random.fold_in(self._seed_key, epoch)

    def batch_key(self, epoch: int, pos: int):
        return jax.random.fold_in(self.epoch_key(epoch), pos)

    # -- per-epoch device state ---------------------------------------------
    def epoch_roots(self, epoch: int) -> jnp.ndarray:
        """The (num_batches * B,) device-resident root order for `epoch`,
        -1 padded (cached; recomputed once per epoch)."""
        if self._order_cache[0] == epoch:
            return self._order_cache[1]
        with obs_trace.span("epoch_order", cat="build", epoch=epoch):
            return self._epoch_roots_fresh(epoch)

    def _epoch_roots_fresh(self, epoch: int) -> jnp.ndarray:
        if self.spec is not None:
            order = device_epoch_order(
                self.spec, epoch_words_for(self.seed, epoch))
        else:
            rng = np.random.default_rng((self.seed, epoch))
            order = jnp.asarray(self.policy.epoch_order(
                self.graph.train_ids, self.graph.communities, rng),
                jnp.int32)
        if order.shape[0] > self.padded_len:      # drop_last truncation
            order = order[:self.padded_len]
        prev = self._order_cache[1]
        if self._donate and prev is not None:
            pad = _pad_into(order, prev)
        else:
            pad = _pad_fresh(order, self.padded_len)
        self._order_cache = (epoch, pad)
        return pad

    def epoch_ranks(self, epoch: int):
        """Shared-randomness sampler state for `epoch`, computed once and
        threaded into every build of the epoch (None for samplers without
        one)."""
        if self._ranks_cache[0] != epoch:
            self._ranks_cache = (epoch, mb.sampler_epoch_ctx(
                self.sampler, self.epoch_key(epoch), self.g))
        return self._ranks_cache[1]

    # -- the fused build ----------------------------------------------------
    def build(self, epoch: int, pos: int) -> mb.MiniBatch:
        """MiniBatch for cursor (epoch, pos) — one jit dispatch, no
        per-batch host->device transfer beyond two int32 scalars."""
        if not 0 <= pos < self.num_batches:
            raise IndexError(
                f"pos {pos} out of range for {self.num_batches} batches")
        # chaos site (repro.resilience): an armed plan makes this build
        # raise InjectedFault — in the async pipeline that kills the
        # producer thread, which the consumer watchdog must absorb by
        # restarting from the same cursor (bit-exact, builds are pure)
        faults.maybe_raise("batch_build", epoch=epoch, pos=pos)
        with obs_trace.span("batch_build", cat="build",
                            epoch=epoch, pos=pos):
            return _fused_build(
                self._seed_key, jnp.asarray(epoch, jnp.int32),
                jnp.asarray(pos, jnp.int32), self.g,
                self.epoch_roots(epoch), self.labels,
                self.epoch_ranks(epoch), self.batch_size,
                self.fanouts, self.caps, self.sampler)


# ---------------------------------------------------------------------------
# per-stage microbenchmark (roots / sample / dedup)
# ---------------------------------------------------------------------------
def _time_us(fn, *args, iters: int = 10) -> float:
    # analysis: allow[no-host-sync-in-hot-path] -- microbenchmark warmup: compiles + drains before timing, never on the training path
    jax.block_until_ready(fn(*args))          # compile + warm
    best = float("inf")
    for _ in range(iters):
        # analysis: allow[no-wall-clock] -- stage-timing instrumentation; timings are reported, never fed back into batch construction
        t0 = time.perf_counter()
        # analysis: allow[no-host-sync-in-hot-path] -- benchmark drain: the measurement IS the sync
        jax.block_until_ready(fn(*args))
        # analysis: allow[no-wall-clock] -- stage-timing instrumentation; timings are reported, never fed back into batch construction
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def stage_times(g: DeviceGraph, roots, labels_all, fanouts, caps, sampler,
                *, key=None, epoch_key=None, iters: int = 10) -> dict:
    """Best-of-`iters` device time (µs) per build stage, on realized
    levels of one representative batch:

      roots_us    root mask + sort (level-0 prep)
      sample_us   all hops' neighbor sampling
      dedup_us    concat + static-size dedup + position maps from its
                  sort, per hop (the build's own `mb._dedup` and
                  `mb._sorted_positions`)

    The stages are timed as separate jits over the SAME intermediates the
    fused builder produces, so the split is apples-to-apples with the
    whole-build numbers in `sampler_sweep/*`.
    """
    fanouts, caps = tuple(fanouts), tuple(caps)
    sampler = sampling.resolve(sampler)
    key = jax.random.key(0) if key is None else key
    epoch_key = key if epoch_key is None else epoch_key
    N = g.num_nodes
    roots = jnp.asarray(roots, jnp.int32)
    shared = mb.sampler_epoch_ctx(sampler, epoch_key, g)

    @jax.jit
    def roots_fn(r):
        m = r >= 0
        return jnp.sort(jnp.where(m, r, N).astype(jnp.int32))

    @jax.jit
    def sample_fn(k, ek, levels):
        keys = jax.random.split(k, len(fanouts))
        out = []
        for h, fan in enumerate(fanouts):
            k_h = ek if sampler.shared_randomness else keys[h]
            if shared is not None:
                out.append(sampler.sample(k_h, g, levels[h], fan,
                                          ranks=shared))
            else:
                out.append(sampler.sample(k_h, g, levels[h], fan))
        return out

    @jax.jit
    def dedup_fn(levels, srcs):
        out = []
        for h, cap in enumerate(caps):
            prev = levels[h]
            s = srcs[h][0].reshape(-1)
            nxt, new, perm = mb._dedup(jnp.concatenate([prev, s]), cap, N)
            out.append((nxt,) + mb._sorted_positions(new, perm, cap))
        return out

    batch = mb._build_batch(key, epoch_key, g, roots, labels_all,
                            fanouts, caps, sampler)
    levels = tuple(jax.block_until_ready(batch.levels))[:-1]
    srcs = jax.block_until_ready(sample_fn(key, epoch_key, levels))
    return {
        "roots_us": _time_us(roots_fn, roots, iters=iters),
        "sample_us": _time_us(sample_fn, key, epoch_key, levels,
                              iters=iters),
        "dedup_us": _time_us(dedup_fn, levels, srcs, iters=iters),
    }
