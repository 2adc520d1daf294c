"""Structured span tracing for the GNN training stack (`repro.obs`).

Every instrumented subsystem (batch builder, producer thread, trainer
step loop, checkpointing, cache refill) emits *spans* — named wall-clock
intervals tagged with a category and free-form args — into one global
`Tracer`. The on-disk format is Chrome-trace/Perfetto-compatible JSONL:
one JSON event object per line, each a complete-duration ("ph": "X") or
instant ("ph": "i") event with microsecond timestamps and real
pid/tid, so a trace answers "what was the producer thread doing while
the train step stalled?" by inspection. `python -m repro.obs` converts
a trace to the `{"traceEvents": [...]}` wrapper ui.perfetto.dev opens
directly, and computes overlap/stall reports from it (`obs/report.py`).

Span taxonomy (categories):

  step      consumer train-step dispatch (`GNNTrainer._train_one`)
  build     device batch build dispatch / epoch-order refresh
            (`batching.stream`, `pipeline.builder`: `batch_build`,
            `epoch_order`)
  producer  the async producer thread's build loop
            (`pipeline.prefetch._produce`)
  wait      blocked time: consumer queue get, producer queue put
  sync      host<->device synchronization points (epoch-boundary flush,
            guard skip-counter sync, cache-refill churn sync,
            checkpoint save) — the analyzer gates that NONE of these
            occur mid-epoch
  cache     dynamic-cache CLOCK refill dispatch
  ckpt      checkpoint restore / rollback
  loop      epoch envelope (`run_epoch`)
  eval      evaluation pass

Zero-cost when disabled: the module-level tracer defaults to None and
`span()` returns a shared no-op context manager (and `instant()` does
nothing) without allocating — the hot path pays one global read, one
`is None` test and one check that no JAX profiler session is recording.
Tracing never syncs the device and never touches RNG or batch data, so
the loss trajectory is bit-identical with tracing on vs off (pinned by
tests/test_obs.py).

On the profiler's clock: while a JAX profiler session records
(`jax.profiler.trace` / `start_trace`), every span is also a TraceMe
(`jax.profiler.TraceAnnotation`) under its bare name on the calling
thread's host line, beside the device's ops, with or without a JSONL
tracer installed. Per-call args (`step=`, `epoch=`) stay in the JSONL
event only, so one span keeps one name in the profile. Device time is
the device trace's own: the batch build's phases and the aggregation
kernels carry `jax.named_scope`s there (`core/minibatch.py`,
`kernels/gather_agg/kernel.py`).
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

TRACE_SCHEMA_VERSION = 1

# required keys of every emitted event; "X" events additionally carry
# "dur" — the conformance contract tests/test_obs.py pins
EVENT_KEYS = ("name", "cat", "ph", "ts", "pid", "tid")


def _now_us() -> float:
    return time.perf_counter_ns() / 1e3


# True while a JAX profiler session records host events (a TraceMe made
# then lands in the profile)
_profiling = TraceAnnotation.is_enabled


class _Span:
    """One in-flight "X" (complete) event; also the reusable context
    manager `Tracer.span` returns. While the JAX profiler records, the
    span is also a TraceMe under its bare name."""
    __slots__ = ("_tracer", "_ev", "_t0", "_tm")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Dict[str, Any]):
        self._tracer = tracer
        self._ev = {"name": name, "cat": cat, "ph": "X", "pid": tracer.pid,
                    "tid": threading.get_ident(), "args": args}
        self._t0 = 0.0
        self._tm = None

    def set(self, **args) -> "_Span":
        """Attach args discovered mid-span (e.g. a result count)."""
        self._ev["args"].update(args)
        return self

    def __enter__(self) -> "_Span":
        if _profiling():
            self._tm = TraceAnnotation(self._ev["name"])
            self._tm.__enter__()
        self._t0 = _now_us()
        return self

    def __exit__(self, *exc) -> None:
        ev = self._ev
        ev["ts"] = self._t0
        ev["dur"] = _now_us() - self._t0
        if self._tm is not None:
            self._tm.__exit__(*exc)
            self._tm = None
        self._tracer._emit(ev)


class _ProfilerSpan:
    """A span while the JAX profiler records and no tracer is installed:
    a TraceMe under the span's bare name, and nothing else."""
    __slots__ = ("_tm",)

    def __init__(self, name: str):
        self._tm = TraceAnnotation(name)

    def set(self, **args) -> "_ProfilerSpan":
        return self

    def __enter__(self) -> "_ProfilerSpan":
        self._tm.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._tm.__exit__(*exc)


class _NoopSpan:
    """Shared do-nothing span: what `span()` hands out when tracing is
    disabled. Stateless, hence safe to share across threads/reentries."""
    __slots__ = ()

    def set(self, **args) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NOOP = _NoopSpan()


class Tracer:
    """Buffered, thread-safe span collector writing Chrome-trace JSONL.

    `path=None` keeps events in memory only (tests, ad-hoc analysis —
    read them back with `events()`); with a path, `flush()`/`close()`
    append the buffered events one JSON object per line. Timestamps are
    microseconds from `perf_counter_ns` (monotonic, sub-us resolution);
    pid/tid are the real process/thread ids so multi-thread traces lay
    out one Perfetto track per thread.
    """

    def __init__(self, path: Optional[str] = None,
                 meta: Optional[Dict[str, Any]] = None):
        self.path = path
        self.pid = os.getpid()
        self.meta = dict(meta or {})
        self._lock = threading.Lock()
        self._buf: List[dict] = []
        self._all: List[dict] = []
        if path:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            # truncate + header: process metadata rides as an "M" event
            with open(path, "w") as f:
                f.write(json.dumps(self._meta_event()) + "\n")

    def _meta_event(self) -> dict:
        return {"name": "process_name", "cat": "__metadata", "ph": "M",
                "ts": 0, "pid": self.pid, "tid": 0,
                "args": dict(self.meta,
                             schema_version=TRACE_SCHEMA_VERSION)}

    # -- emission -----------------------------------------------------------
    def _emit(self, ev: dict) -> None:
        with self._lock:
            self._buf.append(ev)
            self._all.append(ev)

    def span(self, name: str, cat: str = "host", **args) -> _Span:
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "host", **args) -> None:
        self._emit({"name": name, "cat": cat, "ph": "i", "ts": _now_us(),
                    "pid": self.pid, "tid": threading.get_ident(),
                    "s": "t", "args": args})

    def for_replica(self, r: int) -> "_ReplicaView":
        """A pid-view of this tracer for mesh replica `r`: events emitted
        through it carry a pid distinct from the host process (and from
        every other replica), so each replica lays out as its own
        Perfetto process track and `obs.report`'s per-pid mid-epoch-sync
        gate judges each replica's timeline separately. The first use of
        a replica emits its "M" process_name header."""
        views = self.__dict__.setdefault("_replica_views", {})
        view = views.get(r)
        if view is None:
            view = views[r] = _ReplicaView(self, r)
        return view

    # -- inspection / persistence -------------------------------------------
    def events(self) -> List[dict]:
        """All events emitted so far (including already-flushed ones),
        metadata header excluded."""
        with self._lock:
            return list(self._all)

    def flush(self) -> None:
        with self._lock:
            buf, self._buf = self._buf, []
        if self.path and buf:
            with open(self.path, "a") as f:
                for ev in buf:
                    f.write(json.dumps(ev) + "\n")

    def close(self) -> None:
        self.flush()


class _ReplicaView:
    """Per-replica pid facade over a `Tracer` (see `Tracer.for_replica`).

    Spans are emitted with EXPLICIT (ts, dur): replica timelines are
    reconstructed after the fact from per-step host dispatch timestamps
    plus the sharded step's per-replica aux outputs
    (`dist.gnn.ReplicaTraceEmitter`), never timed live — an SPMD step is
    one dispatch for all replicas, so live per-replica wall timing does
    not exist. Emission itself never syncs the device."""
    __slots__ = ("_tracer", "replica", "pid")

    def __init__(self, tracer: Tracer, r: int):
        self._tracer = tracer
        self.replica = r
        # distinct from the host pid and from every other replica view
        self.pid = tracer.pid * 1000 + r + 1
        tracer._emit({"name": "process_name", "cat": "__metadata",
                      "ph": "M", "ts": 0, "pid": self.pid, "tid": 0,
                      "args": {"name": f"replica {r}", "replica": r}})

    def emit_span(self, name: str, cat: str, ts: float, dur: float,
                  **args) -> None:
        self._tracer._emit({"name": name, "cat": cat, "ph": "X",
                            "ts": ts, "dur": dur, "pid": self.pid,
                            "tid": 0, "args": dict(args,
                                                   replica=self.replica)})

    def instant(self, name: str, cat: str = "host", **args) -> None:
        self._tracer._emit({"name": name, "cat": cat, "ph": "i",
                            "ts": _now_us(), "pid": self.pid, "tid": 0,
                            "s": "t",
                            "args": dict(args, replica=self.replica)})


# ---------------------------------------------------------------------------
# global tracer: the stack's call sites go through these free functions
# ---------------------------------------------------------------------------
_TRACER: Optional[Tracer] = None


def install(tracer: Tracer) -> Tracer:
    """Make `tracer` the stack-wide tracer (visible from every thread)."""
    global _TRACER
    _TRACER = tracer
    return tracer


def uninstall() -> Optional[Tracer]:
    """Disable tracing; returns (and flushes) the previous tracer."""
    global _TRACER
    t, _TRACER = _TRACER, None
    if t is not None:
        t.flush()
    return t


def current() -> Optional[Tracer]:
    return _TRACER


class enabled:
    """`with trace.enabled("t.jsonl") as t:` — install for the block."""

    def __init__(self, path: Optional[str] = None, **meta):
        self.tracer = Tracer(path, meta=meta)

    def __enter__(self) -> Tracer:
        return install(self.tracer)

    def __exit__(self, *exc) -> None:
        if _TRACER is self.tracer:
            uninstall()
        else:                       # someone swapped tracers mid-block
            self.tracer.flush()


def span(name: str, cat: str = "host", **args):
    """A span on the installed tracer and, while the JAX profiler
    records, on the profile's host line; the shared no-op when neither
    records — the ONE line hot paths pay."""
    t = _TRACER
    if t is None:
        return _ProfilerSpan(name) if _profiling() else NOOP
    return t.span(name, cat, **args)


def instant(name: str, cat: str = "host", **args) -> None:
    t = _TRACER
    if t is not None:
        t.instant(name, cat, **args)
