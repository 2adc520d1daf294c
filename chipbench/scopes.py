"""Device time under a program's named scopes, read from a JAX profile.

A TPU profile gives each op on a chip's `XLA Ops` line the op name it
was traced under, its `jax.named_scope` path, as the stat `tf_op` of the
op's event metadata, e.g.
`jit(_build_batch)/build/hop0/positions/jit(searchsorted)/.../gather:`.
`jax.profiler.ProfileData` shows an event's own stats but not those of
its metadata, so this module reads the `.xplane.pb` itself, through the
few fields of the XPlane schema (`tsl/profiler/protobuf/xplane.proto`)
it needs.

As a v5e profile has it (checked by hand, see PERF.md): fusions, sorts
and Pallas kernels carry `tf_op`; a `while` carries none, but the ops of
its body do, so a loop counts as the union of its body's ops (the loop's
own control between them goes unnamed); the compiler's copies carry
none.

A per-layer metric calls `scope_ns(ctx, patterns)`: the union of the
intervals of the ops whose op name matches, clipped to the traced
window and averaged over the chips that ran ops in it. Ops nest (a
`while` and its body's fusions), so their time is never summed. The
profile is the one `run.py` wrote for this run (its `--trace-dir`, or
its default directory); a profile whose window is not the run's is not
read.
"""
from __future__ import annotations

import argparse
import re
import sys
from typing import Dict, List, Tuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from chipbench import trace_reduce as tr

WINDOW = "chipbench.window"
OP_NAME_STAT = "tf_op"

Op = Tuple[str, float, float]             # (op name, start_ns, end_ns)

# (message, [(field, number, type, repeated, message type)]) of the
# XPlane schema, as far as it is read here
_INT64, _STRING, _UINT64, _MESSAGE = 3, 9, 4, 11
_SCHEMA = [
    ("XStat", [("metadata_id", 1, _INT64, False, None),
               ("str_value", 5, _STRING, False, None),
               ("ref_value", 7, _UINT64, False, None)]),
    ("XEvent", [("metadata_id", 1, _INT64, False, None),
                ("offset_ps", 2, _INT64, False, None),
                ("duration_ps", 3, _INT64, False, None)]),
    ("XLine", [("name", 2, _STRING, False, None),
               ("timestamp_ns", 3, _INT64, False, None),
               ("events", 4, _MESSAGE, True, "XEvent")]),
    ("XEventMetadata", [("id", 1, _INT64, False, None),
                        ("name", 2, _STRING, False, None),
                        ("stats", 5, _MESSAGE, True, "XStat")]),
    ("XStatMetadata", [("id", 1, _INT64, False, None),
                       ("name", 2, _STRING, False, None)]),
    # the schema's maps, as the repeated entries they are on the wire
    ("EventMetadataEntry", [("key", 1, _INT64, False, None),
                            ("value", 2, _MESSAGE, False,
                             "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, _INT64, False, None),
                           ("value", 2, _MESSAGE, False, "XStatMetadata")]),
    ("XPlane", [("name", 2, _STRING, False, None),
                ("lines", 3, _MESSAGE, True, "XLine"),
                ("event_metadata", 4, _MESSAGE, True, "EventMetadataEntry"),
                ("stat_metadata", 5, _MESSAGE, True, "StatMetadataEntry")]),
    ("XSpace", [("planes", 1, _MESSAGE, True, "XPlane")]),
]
_PACKAGE = "chipbench.xplane"


def _message_classes() -> Dict[str, type]:
    fd = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto",
                                            package=_PACKAGE,
                                            syntax="proto3")
    for name, fields in _SCHEMA:
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated, sub in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=3 if repeated else 1)
            if sub:
                f.type_name = f".{_PACKAGE}.{sub}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.{name}"))
        for name, _ in _SCHEMA}


MESSAGES = _message_classes()


def load(path: str):
    """The `XSpace` of a `.xplane.pb` file."""
    space = MESSAGES["XSpace"]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _events(plane, line_match):
    """(event metadata, start_ns, end_ns) of the plane's matching lines,
    in whole ns as `ProfileData` gives them (so the window is the one
    `run.py` reads)."""
    meta = {e.key: e.value for e in plane.event_metadata}
    for line in plane.lines:
        if not line_match(line.name):
            continue
        for ev in line.events:
            start = float(line.timestamp_ns + ev.offset_ps // 1000)
            yield (meta.get(ev.metadata_id), start,
                   start + ev.duration_ps // 1000)


def op_name(plane, metadata) -> str:
    """The op name an XLA op was traced under ('' where it has none)."""
    names = {s.key: s.value.name for s in plane.stat_metadata}
    for s in metadata.stats:
        if names.get(s.metadata_id) == OP_NAME_STAT:
            return s.str_value or names.get(s.ref_value, "")
    return ""


def device_ops(space, plane_prefix: str = tr.TPU_PLANE,
               ops_line: str = tr.OPS_LINE) -> List[List[Op]]:
    """Per chip, each op on its ops line with its op name."""
    out = []
    for plane in space.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        names: Dict[int, str] = {}
        ops = []
        for md, s, e in _events(plane, lambda n: n == ops_line):
            if md is None:
                continue
            if md.id not in names:
                names[md.id] = op_name(plane, md)
            ops.append((names[md.id], s, e))
        out.append(ops)
    return out


def host_window(space, name: str = WINDOW) -> Tuple[float, float]:
    """[start, end] of the first host span called `name` on the host's
    Python line (see `trace_reduce.host_events`)."""
    for plane in space.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for md, s, e in _events(plane,
                                lambda n: n.startswith(tr.HOST_LINE)):
            if md is not None and md.name == name:
                return s, e
    raise RuntimeError(f"no host span {name!r} in the trace")


def matcher(patterns):
    """A test of op names: true where a whole segment run of the name
    (between `/` and `/`, or the name's trailing `:`) matches one of the
    patterns, `*` standing for any text inside one segment. So
    `build/hop*/dedup` matches `jit(f)/build/hop1/dedup/jit(unique)/sort:`
    and `gather_agg_dx` matches `.../gather_agg_dx/pallas_call:`."""
    alts = "|".join(re.escape(p).replace(r"\*", "[^/]*") for p in patterns)
    rx = re.compile(rf"(?:^|/)(?:{alts})(?:/|:|$)")
    return lambda name: rx.search(name) is not None


def scope_ns_of(chips: List[List[Op]], patterns, lo: float,
                hi: float) -> float:
    """Union of the intervals of the ops whose name matches, inside
    [lo, hi], averaged over the chips that ran any op there."""
    match = matcher(patterns)
    busy = [ops for ops in chips
            if tr.clip(tr.merge((s, e) for _, s, e in ops), lo, hi)]
    if not busy:
        return 0.0
    return sum(sum(e - s for s, e in tr.clip(tr.merge(
        (s, e) for n, s, e in ops if match(n)), lo, hi))
        for ops in busy) / len(busy)


def trace_dir(ctx) -> str:
    """Where this run's profile is: `--trace-dir`, else `run.py`'s
    default directory."""
    # no abbreviations: `--trace 1` is no `--trace-dir`
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--trace-dir", default=None)
    args, _ = ap.parse_known_args(sys.argv[1:])
    return args.trace_dir or str(ctx.layout.dir / ".cache" / "trace")


def _profile(ctx):
    """(chips' named ops, lo, hi) of this run's profile, or None where
    there is no profile of this run's window. Each reader parses the
    profile anew (under a second at full size)."""
    try:
        space = load(tr.find_xplane(trace_dir(ctx)))
        lo, hi = host_window(space)
    except RuntimeError:
        return None
    # another run's profile has another window
    if abs((hi - lo) - ctx.window_ns) > 1.0:
        return None
    return device_ops(space), lo, hi


def scope_ns(ctx, patterns) -> float:
    """Device time (ns, averaged over the chips) under the named scopes
    `patterns` in this run's traced window; 0 where the profile has none
    (a program without the scopes, or no profile of this run)."""
    prof = _profile(ctx)
    if prof is None:
        return 0.0
    chips, lo, hi = prof
    return scope_ns_of(chips, patterns, lo, hi)
