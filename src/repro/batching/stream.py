"""Resumable streams of compiled `MiniBatch`es.

`BatchStream` is the single entry point for GNN batch construction: it owns
the per-epoch root ordering (via a `BatchPolicy`), the jit-compiled static
batch builder, and an explicit `Cursor(epoch, pos)` that goes into every
checkpoint — the same resume contract `LMStream` has for the LM corpus.

Determinism contract: everything is derived from `(seed, epoch, pos)` —
the numpy epoch order from `default_rng((seed, epoch))`, the device
sampling key from `fold_in(fold_in(key(seed), epoch), pos)`. A stream
restored mid-epoch from a cursor therefore reproduces the continuation
bit-exactly, with no RNG state in the checkpoint beyond the cursor itself.
Shared-randomness samplers (LABOR) additionally receive the EPOCH-level
key `fold_in(key(seed), epoch)`, also a pure function of the cursor.

Neighbor sampling is pluggable: the stream resolves the policy's
`sampler_spec()` through `repro.sampling` (override with `sampler=`), and
the sampler rides into the jit-compiled builder as a static argument.

Prefetch: while the consumer runs step i, the builder for batch i+1 has
already been dispatched (jit dispatch is async), overlapping host batch
assembly + host->device transfer with device compute.

Feature cache: `cache=` attaches a `repro.featcache` cache (a static
`CachePlan`, a dynamic CLOCK `DynamicCacheState`, an admission-policy
name, or `"dynamic[:admission]"` — normalized by `featcache.as_cache`
against this stream's policy/shape) to the stream; consumers route
layer-0 feature reads through it (`gather_cached`) and measure hit
rates. A dynamic cache is MUTABLE trainer state: `GNNTrainer` re-assigns
`stream.cache` as the state evolves, so the stream always carries the
current residency.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sampling
from repro.batching.order import make_batches
from repro.batching.policy import BatchPolicy, as_policy
from repro.core import minibatch as mb
from repro.graphs.csr import DeviceGraph, Graph
from repro.obs import trace as obs_trace


@dataclass
class Cursor:
    """Stream position: epoch number + batch index within the epoch."""
    epoch: int = 0
    pos: int = 0

    def state(self) -> dict:
        return {"epoch": self.epoch, "pos": self.pos}

    @staticmethod
    def from_state(d) -> "Cursor":
        return Cursor(int(d["epoch"]), int(d["pos"]))


class BatchStream:
    """Policy-driven, cursor-resumable stream of compiled `MiniBatch`es."""

    def __init__(self, graph: Graph, policy, batch_size: int, fanouts,
                 caps, *, seed: int = 0, cursor: Optional[Cursor] = None,
                 drop_last: bool = False, sampler=None,
                 mode: str = "sample",
                 device_graph: Optional[DeviceGraph] = None,
                 labels: Optional[jnp.ndarray] = None,
                 dispatch_ahead: bool = True, cache=None,
                 prefetch=None):
        self.graph = graph
        self.policy: BatchPolicy = as_policy(policy)
        self.batch_size = batch_size
        self.fanouts = tuple(fanouts)
        self.caps = tuple(caps)
        self.seed = seed
        self.cursor = cursor or Cursor()
        self.drop_last = drop_last
        # sampler=None binds the policy's own sampler_spec(); mode="all" is
        # the deprecated string knob for the full-neighborhood sampler
        self.sampler = sampling.resolve(
            sampler, mode, lambda: sampling.for_policy(self.policy))
        # the device feature cache riding with the stream: any
        # `featcache.as_cache` spec (static plan, dynamic CLOCK state, or
        # name, built here against this stream's policy/shape) that
        # consumers gather layer-0 features through — `GNNTrainer` reads
        # it back off the stream and keeps it current as dynamic
        # admission evolves the state
        self.cache = None
        if cache is not None:
            from repro import featcache
            self.cache = featcache.as_cache(
                cache, graph, policy=self.policy, batch_size=batch_size,
                fanouts=self.fanouts, seed=seed)
        if prefetch is not None:
            # the old name oversold a single-slot async DISPATCH as
            # prefetching — real depth-k prefetch on a background thread
            # is `repro.pipeline.AsyncBatchStream`
            warnings.warn(
                "BatchStream(prefetch=...) is deprecated: the flag only "
                "controls single-slot async dispatch and is now named "
                "dispatch_ahead=; for actual background prefetching use "
                "repro.pipeline.AsyncBatchStream", DeprecationWarning,
                stacklevel=2)
            dispatch_ahead = prefetch
        self.dispatch_ahead = dispatch_ahead
        self.g = device_graph or DeviceGraph.from_graph(graph)
        self.labels = labels if labels is not None \
            else jnp.asarray(graph.labels)
        self._order_cache = (-1, None)        # (epoch, (n_batches, B) roots)
        self._epoch_ctx = (-1, None)          # (epoch, shared sampler state)
        self._prefetched = None               # (epoch, pos, MiniBatch)

    # -- deterministic derivations ------------------------------------------
    def root_batches(self, epoch: int) -> np.ndarray:
        """Root-id batches for `epoch` (cached for the current epoch)."""
        if self._order_cache[0] != epoch:
            with obs_trace.span("epoch_order", cat="build", epoch=epoch):
                rng = np.random.default_rng((self.seed, epoch))
                order = self.policy.epoch_order(
                    self.graph.train_ids, self.graph.communities, rng)
                self._order_cache = (epoch, make_batches(
                    order, self.batch_size, self.drop_last))
        return self._order_cache[1]

    def num_batches(self, epoch: int = None) -> int:
        # closed form (every epoch visits the full train set), so async
        # consumers can size an epoch without materializing its order
        n = len(self.graph.train_ids)
        return n // self.batch_size if self.drop_last \
            else -(-n // self.batch_size)

    def epoch_key(self, epoch: int):
        """Epoch-level PRNG key — what shared-randomness samplers (LABOR)
        draw from, so picks repeat across the epoch's batches and hops."""
        return jax.random.fold_in(jax.random.key(self.seed), epoch)

    def batch_key(self, epoch: int, pos: int):
        """PRNG key for batch (epoch, pos) — pure function of the cursor."""
        return jax.random.fold_in(self.epoch_key(epoch), pos)

    def epoch_ctx(self, epoch: int):
        """Per-epoch shared sampler state (LABOR's node ranks), computed
        ONCE per epoch and threaded into every build — previously the
        ranks were re-hashed inside every batch build."""
        if self._epoch_ctx[0] != epoch:
            self._epoch_ctx = (epoch, mb.sampler_epoch_ctx(
                self.sampler, self.epoch_key(epoch), self.g))
        return self._epoch_ctx[1]

    def build(self, roots: np.ndarray, epoch: int, pos: int) -> mb.MiniBatch:
        """Compile/dispatch the static-shape batch for these roots."""
        with obs_trace.span("batch_build", cat="build",
                            epoch=epoch, pos=pos):
            return mb._build_batch(
                self.batch_key(epoch, pos), self.epoch_key(epoch), self.g,
                jnp.asarray(roots, jnp.int32), self.labels, self.fanouts,
                self.caps, self.sampler, self.epoch_ctx(epoch))

    # -- iteration -----------------------------------------------------------
    def _take(self, epoch: int, pos: int) -> mb.MiniBatch:
        """Produce batch (epoch, pos) — the override point for async
        streams. The base class consumes its single dispatched-ahead slot
        or builds synchronously from the numpy epoch order."""
        if self._prefetched is not None and \
                self._prefetched[:2] == (epoch, pos):
            batch = self._prefetched[2]
            self._prefetched = None
            return batch
        return self.build(self.root_batches(epoch)[pos], epoch, pos)

    def _dispatch_ahead(self, epoch: int, pos: int) -> None:
        """Fire off batch (epoch, pos) so it overlaps the consumer's
        current step (async jit dispatch; no-op in async streams, which
        have a real queue)."""
        if self.dispatch_ahead:
            self._prefetched = (epoch, pos,
                                self.build(self.root_batches(epoch)[pos],
                                           epoch, pos))

    def epoch(self) -> Iterator[mb.MiniBatch]:
        """Yield the REMAINDER of the current epoch (all of it when the
        cursor sits at pos 0), then advance the cursor to the next epoch.
        After each yield the cursor already points at the next batch, so a
        checkpoint taken mid-iteration resumes after the consumed batch."""
        nb = self.num_batches(self.cursor.epoch)
        if nb and self.cursor.pos >= nb:
            # a consumer stopped exactly on the epoch boundary: normalize
            self.cursor.epoch += 1
            self.cursor.pos = 0
            self._prefetched = None
            nb = self.num_batches(self.cursor.epoch)
        if nb == 0:
            # empty train set, or drop_last with fewer roots than a batch —
            # raising beats __iter__ spinning forever on empty epochs
            raise ValueError(
                f"epoch {self.cursor.epoch} has no batches "
                f"({len(self.graph.train_ids)} train ids, batch_size="
                f"{self.batch_size}, drop_last={self.drop_last})")
        e = self.cursor.epoch
        while self.cursor.epoch == e and self.cursor.pos < nb:
            pos = self.cursor.pos
            batch = self._take(e, pos)
            self.cursor.pos += 1
            if self.cursor.pos < nb:
                self._dispatch_ahead(e, self.cursor.pos)
            yield batch
        if self.cursor.epoch == e:            # exhausted, not broken out of
            self.cursor.epoch += 1
            self.cursor.pos = 0
            self._prefetched = None

    def __iter__(self) -> Iterator[mb.MiniBatch]:
        while True:
            yield from self.epoch()


def eval_batches(graph: Graph, ids: np.ndarray, batch_size: int, fanouts,
                 caps, p: float = 0.5, *, seed: int = 0,
                 sampler=None, mode: str = "sample",
                 device_graph: Optional[DeviceGraph] = None,
                 labels: Optional[jnp.ndarray] = None
                 ) -> Iterator[mb.MiniBatch]:
    """Deterministic sequential batches over `ids` (padded with -1), with
    one-batch prefetch. Keys derive from (seed, chunk index) only, so
    evaluation never perturbs training RNG state. `sampler=None` keeps the
    biased two-phase draw at `p` (the uniform-eval contract); `mode="all"`
    is the deprecated knob for the full-neighborhood sampler."""
    g = device_graph or DeviceGraph.from_graph(graph)
    labels = labels if labels is not None else jnp.asarray(graph.labels)
    fanouts, caps = tuple(fanouts), tuple(caps)
    sampler = sampling.resolve(
        sampler, mode, lambda: sampling.BiasedTwoPhaseSampler(p=float(p)))
    key = jax.random.key(seed)
    chunks = []
    for i in range(0, len(ids), batch_size):
        pad = np.full(batch_size, -1, np.int64)
        chunk = ids[i:i + batch_size]
        pad[:len(chunk)] = chunk
        chunks.append(pad)

    def build(j):
        return mb.build_batch(
            jax.random.fold_in(key, j), g, jnp.asarray(chunks[j], jnp.int32),
            labels, fanouts, caps, sampler, epoch_key=key)

    nxt = build(0) if chunks else None
    for j in range(len(chunks)):
        cur, nxt = nxt, (build(j + 1) if j + 1 < len(chunks) else None)
        yield cur
