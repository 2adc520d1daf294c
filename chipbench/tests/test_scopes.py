"""`scopes` on a synthetic profile: device time under named scopes is the
union of the matching ops' intervals (a `while` and the fusion nested in
it count once), clipped to the window and averaged over the busy chips,
and the per-layer readers find it. Times in ns."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import scopes
from chipbench.layout import Layout

REPO = Path(__file__).resolve().parents[2]
LO, HI = 20, 1020
POS = "jit(_build_batch)/build/hop{}/positions/jit(searchsorted)/{}"

# chip 0: (op name, start, end); "" is an op without an op name
CHIP0 = [
    (POS.format(0, "while"), 100, 400),           # a loop ...
    (POS.format(0, "while/body/gather"), 150, 250),   # ... and its body
    (POS.format(1, "min"), 300, 380),             # inside the loop too
    (POS.format(2, "min"), 0, 60),                # cut by the window
    ("jit(_build_batch)/build/hop0/dedup/jit(unique)/sort", 400, 450),
    ("jit(_build_batch)/build/hop1/sample/jit(sample)/gather", 450, 470),
    ("jit(train_step)/jvp(gather_agg_fwd)/gather_agg_fwd/pallas_call",
     500, 560),
    ("jit(train_step)/transpose(jvp(gather_agg_dx))/gather_agg_dx/"
     "pallas_call", 560, 600),
    ("", 600, 700),                               # a compiler's copy
    ("jit(train_step)/jvp(dense)/dot_general", 700, 800),
]
# chip 1: one kernel; chip 2 runs nothing in the window
CHIP1 = [("jit(train_step)/transpose(jvp(gather_agg_dw))/gather_agg_dw/"
          "pallas_call", 100, 200)]
CHIP2 = [("jit(other)/build/hop0/positions/x", 2000, 3000)]


def _plane(space, name, ops, *, ref: bool):
    """A device plane whose op names ride as `str_value`, or as a
    `ref_value` into the stat metadata (both occur in profiles)."""
    p = space.planes.add(name=name)
    stat = p.stat_metadata.add(key=1)
    stat.value.id, stat.value.name = 1, "tf_op"
    ln = p.lines.add(name="XLA Ops", timestamp_ns=0)
    for i, (op, s, e) in enumerate(ops, start=10):
        md = p.event_metadata.add(key=i).value
        md.id, md.name = i, f"%op.{i}"
        if op and ref:
            ref_id = 1000 + i
            sm = p.stat_metadata.add(key=ref_id)
            sm.value.id, sm.value.name = ref_id, op + ":"
            md.stats.add(metadata_id=1, ref_value=ref_id)
        elif op:
            md.stats.add(metadata_id=1, str_value=op + ":")
        ln.events.add(metadata_id=i, offset_ps=s * 1000,
                      duration_ps=(e - s) * 1000)


def _space():
    space = scopes.MESSAGES["XSpace"]()
    _plane(space, "/device:TPU:0", CHIP0, ref=False)
    _plane(space, "/device:TPU:1", CHIP1, ref=True)
    _plane(space, "/device:TPU:2", CHIP2, ref=False)
    host = space.planes.add(name="/host:CPU")
    md = host.event_metadata.add(key=1).value
    md.id, md.name = 1, scopes.WINDOW
    host.lines.add(name="python3", timestamp_ns=0).events.add(
        metadata_id=1, offset_ps=LO * 1000, duration_ps=(HI - LO) * 1000)
    return space


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    """A run's reader context whose default trace directory holds the
    synthetic profile."""
    root = tmp_path_factory.mktemp("run") / "chipbench"
    d = root / ".cache" / "trace" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_space().SerializeToString())
    return SimpleNamespace(layout=SimpleNamespace(dir=root), steps=2,
                           window_ns=float(HI - LO))


def test_parsed_ops_and_window():
    space = scopes.MESSAGES["XSpace"].FromString(
        _space().SerializeToString())
    chips = scopes.device_ops(space)
    assert [[(n.rstrip(":"), s, e) for n, s, e in ops] for ops in chips] \
        == [CHIP0, CHIP1, CHIP2]
    assert scopes.host_window(space) == (LO, HI)
    with pytest.raises(RuntimeError):
        scopes.host_window(space, "no such span")


def test_nested_ops_count_once():
    chips = scopes.device_ops(_space())
    # 100..400 holds the body (150..250) and hop 1's op (300..380); hop
    # 2's op is cut to 20..60; chip 2 is idle in the window: (340 + 0)/2
    assert scopes.scope_ns_of(chips, ("build/hop*/positions",), LO, HI) \
        == (300 + 40 + 0) / 2
    assert scopes.scope_ns_of(chips, ("build/hop0/positions",), LO, HI) \
        == 300 / 2
    assert scopes.scope_ns_of(chips, ("build/hop*/dedup",), LO, HI) == 25
    # the kernels, each a scope of its own, on both busy chips
    assert scopes.scope_ns_of(
        chips, ("gather_agg_fwd", "gather_agg_dx", "gather_agg_dw"),
        LO, HI) == (100 + 100) / 2
    # a pattern matches whole segments only: `jvp(gather_agg_fwd)` alone
    # is no `gather_agg` scope
    assert scopes.scope_ns_of(chips, ("gather_agg",), LO, HI) == 0
    # a window in which chip 0 runs a dot alone
    assert scopes.scope_ns_of(chips, ("build/hop*/positions",),
                              750, HI) == 0


def test_readers_find_the_run_profile(ctx):
    lay = Layout(REPO)
    read = {m: lay.module("metrics", m).read(ctx) for m in (
        "build_sample_ms_per_step", "build_dedup_ms_per_step",
        "build_positions_ms_per_step", "gather_agg_ms_per_step")}
    assert read == {"build_sample_ms_per_step": 10 / 1e6 / 2,
                    "build_dedup_ms_per_step": 25 / 1e6 / 2,
                    "build_positions_ms_per_step": 170 / 1e6 / 2,
                    "gather_agg_ms_per_step": 100 / 1e6 / 2}


def test_nothing_to_read_is_none(ctx, tmp_path):
    lay = Layout(REPO)
    positions = lay.module("metrics", "build_positions_ms_per_step")
    # another run's window: the profile is not this run's
    assert positions.read(SimpleNamespace(
        **{**vars(ctx), "window_ns": ctx.window_ns + 7})) is None
    # no profile at all
    assert positions.read(SimpleNamespace(
        **{**vars(ctx), "layout": SimpleNamespace(dir=tmp_path)})) is None
    # a program without the scopes (the parent of this change)
    chips = scopes.device_ops(_space())
    assert scopes.scope_ns_of(chips, ("build/hop*/nothing",), LO, HI) == 0


@pytest.mark.parametrize("argv, where", [
    (["--workload", "c", "--seed", "7", "--trace", "1"], None),
    (["--trace", "1", "--trace-dir", "/elsewhere"], "/elsewhere"),
    (["--trace-dir=/elsewhere", "--trace", "1"], "/elsewhere"),
])
def test_trace_dir_is_the_runs(ctx, monkeypatch, argv, where):
    """The profile is read where `run.py` wrote it: its `--trace-dir`, or
    its default directory (`--trace 1` alone names no directory)."""
    monkeypatch.setattr("sys.argv", ["chipbench/run.py"] + argv)
    assert scopes.trace_dir(ctx) == \
        (where or str(ctx.layout.dir / ".cache" / "trace"))
