"""Reduce a JAX profiler trace (`.xplane.pb`) to the numbers the
per-layer metrics read.

Planes and lines, as a TPU trace lays them out (checked by hand on a
v5e trace, see PERF.md):

- each chip is a plane `/device:TPU:<n>`; its line `XLA Ops` holds one
  event per executed HLO op (Pallas kernels appear under their kernel
  function's name), and its line `XLA Modules` one event per executed
  program, named after the jitted function (`jit_train_step(<id>)`);
- the host plane `/host:CPU` has a line for the Python thread, named
  after the interpreter (`python3` on the chip's host), with the
  benchmark's `TraceAnnotation`s and JAX's dispatch events.

Device and host events share one clock. Nothing here knows a program's
names: callers pass the patterns they read.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

TPU_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_LINE = "python"

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclass
class Event:
    name: str
    start: float                          # ns
    dur: float                            # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Device:
    """One chip's events."""
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def devices(pd, plane_match: Callable[[str], bool] = None,
            ops_line: Callable[[str], bool] = None,
            modules_line: Callable[[str], bool] = None) -> List[Device]:
    """Device events of a `jax.profiler.ProfileData`, one entry per
    matching plane. The defaults select a TPU trace's planes and lines."""
    plane_match = plane_match or (lambda n: n.startswith(TPU_PLANE))
    ops_line = ops_line or (lambda n: n == OPS_LINE)
    modules_line = modules_line or (lambda n: n == MODULES_LINE)
    out = []
    for plane in pd.planes:
        if not plane_match(plane.name):
            continue
        d = Device()
        for line in plane.lines:
            if ops_line(line.name):
                d.ops += _events(line)
            elif modules_line(line.name):
                d.modules += _events(line)
        out.append(d)
    return out


def host_events(pd, plane: str = HOST_PLANE,
                line: str = HOST_LINE) -> List[Event]:
    """Events of the host's Python thread: the line named `line`, or
    `line` plus a suffix (the process name, e.g. `python3`)."""
    out = []
    for p in pd.planes:
        if p.name == plane:
            for ln in p.lines:
                if ln.name.startswith(line):
                    out += _events(ln)
    return out


def merge(intervals) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones (empty
    intervals, the trace's instant markers, are dropped)."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals inside [lo, hi]."""
    return sum(e - s for s, e in
               clip(merge((ev.start, ev.end) for ev in events), lo, hi))


def idle_gaps(events: List[Event], lo: float, hi: float) -> List[Interval]:
    """The intervals of [lo, hi] in which no event runs."""
    gaps, t = [], lo
    for s, e in clip(merge((ev.start, ev.end) for ev in events), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


_ID_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """`jit_train_step(42)` -> `jit_train_step`."""
    return _ID_SUFFIX.sub("", name)


def sum_ns(events: List[Event], lo: float, hi: float,
           key: Callable[[str], Optional[str]]) -> Dict[str, float]:
    """Summed duration inside [lo, hi] per `key(event name)`; events whose
    key is None are skipped."""
    out: Dict[str, float] = {}
    for ev in events:
        k = key(ev.name)
        if k is None:
            continue
        d = min(ev.end, hi) - max(ev.start, lo)
        if d > 0:
            out[k] = out.get(k, 0.0) + d
    return out


def matching_ns(events: List[Event], lo: float, hi: float,
                patterns) -> float:
    """Summed duration inside [lo, hi] of events whose name contains any
    of `patterns`."""
    return sum(sum_ns(events, lo, hi,
                      lambda n: "x" if any(p in n for p in patterns)
                      else None).values())


def host_context(host: List[Event], t: float) -> str:
    """Name of the innermost host event covering time t ('none')."""
    best = None
    for ev in host:
        if ev.start <= t <= ev.end and (best is None or ev.dur < best.dur):
            best = ev
    return best.name if best is not None else "none"


def window(host: List[Event], name: str) -> Interval:
    """[start, end] of the first host span called `name`."""
    for ev in host:
        if ev.name == name:
            return ev.start, ev.end
    raise RuntimeError(f"no host span {name!r} in the trace")
