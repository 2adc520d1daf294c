"""`repro.obs` suite: tracer round-trip + Perfetto conformance, analyzer
arithmetic on synthetic span sets, MetricsHub primitives + export schema,
meter-absorption equivalence on a real trainer run, and the zero-cost
contract (tracing on vs off is loss-bit-identical)."""
import json
import threading

import jax
import numpy as np
import pytest

from repro.obs import metrics as M
from repro.obs import report as R
from repro.obs import trace as T
from repro.obs.__main__ import main as obs_cli
from repro.resilience import soak
from repro.train.monitor import (HitRateMeter, ResilienceMeter,
                                 StragglerMonitor)


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends with tracing disabled."""
    T.uninstall()
    yield
    T.uninstall()


# ---------------------------------------------------------------------------
# tracer: JSONL round-trip + conformance
# ---------------------------------------------------------------------------
def test_trace_jsonl_roundtrip(tmp_path):
    p = str(tmp_path / "t.jsonl")
    with T.enabled(p, run="unit") as tr:
        with T.span("alpha", cat="step", step=3):
            pass
        with T.span("beta", cat="sync") as s:
            s.set(found=7)
        T.instant("tick", cat="host", k=1)
        tr.flush()
    evs = R.load_trace(p)
    assert [e["name"] for e in evs] == ["alpha", "beta", "tick"]
    a, b, i = evs
    assert a["cat"] == "step" and a["ph"] == "X" and a["dur"] >= 0
    assert a["args"]["step"] == 3
    assert b["args"]["found"] == 7          # set() attached mid-span
    assert i["ph"] == "i"
    # metadata header is skipped by default, present on request
    with_meta = R.load_trace(p, include_meta=True)
    assert with_meta[0]["ph"] == "M"
    assert with_meta[0]["args"]["schema_version"] == T.TRACE_SCHEMA_VERSION
    assert with_meta[0]["args"]["run"] == "unit"


def test_trace_conformance_and_chrome_wrapper(tmp_path):
    p = str(tmp_path / "t.jsonl")
    with T.enabled(p) as tr:
        for i in range(5):
            with T.span(f"s{i}", cat="step"):
                pass
        tr.flush()
    evs = R.load_trace(p)
    assert R.validate_events(evs) == []
    out = str(tmp_path / "chrome.json")
    R.to_chrome(evs, out)
    wrapped = json.loads(open(out).read())
    assert set(wrapped) == {"traceEvents", "displayTimeUnit"}
    assert len(wrapped["traceEvents"]) == 5
    for ev in wrapped["traceEvents"]:       # the Perfetto-required keys
        assert {"name", "cat", "ph", "ts", "pid", "tid",
                "dur"} <= set(ev)


def test_validate_events_flags_malformed():
    bad = [{"name": "x", "cat": "c", "ph": "X", "ts": 0.0, "pid": 1,
            "tid": 1},                       # X without dur
           {"cat": "c", "ph": "i", "ts": 0.0, "pid": 1, "tid": 1},  # no name
           {"name": "y", "cat": "c", "ph": "X", "ts": 0.0, "pid": 1,
            "tid": 1, "dur": -3.0}]          # negative dur
    problems = R.validate_events(bad)
    assert len(problems) == 3


def test_load_trace_raises_on_torn_line(tmp_path):
    p = tmp_path / "torn.jsonl"
    p.write_text('{"name": "ok", "cat": "c", "ph": "i", "ts": 0, '
                 '"pid": 1, "tid": 1}\n{"name": "torn", "ca\n')
    with pytest.raises(ValueError, match="bad trace line"):
        R.load_trace(str(p))


def test_disabled_tracing_is_noop_singleton():
    assert T.current() is None
    s = T.span("anything", cat="step", x=1)
    assert s is T.NOOP                      # no allocation when disabled
    with s as inner:
        inner.set(y=2)                      # chainable, does nothing
    T.instant("nothing")                    # no tracer: swallowed


def test_tracer_multithread_tids():
    with T.enabled(None) as tr:
        with T.span("main_work", cat="step"):
            pass

        def worker():
            with T.span("thread_work", cat="producer"):
                pass

        th = threading.Thread(target=worker)
        th.start()
        th.join()
        evs = tr.events()
    tids = {e["name"]: e["tid"] for e in evs}
    assert tids["main_work"] != tids["thread_work"]


def _profile_host_names(log_dir) -> list:
    """Event names on the host Python thread's line of a recorded
    profile (`python`, or `python3` as the interpreter is named)."""
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return [e.name for p in pd.planes if p.name == "/host:CPU"
            for ln in p.lines if ln.name.startswith("python")
            for e in ln.events]


@pytest.mark.parametrize("jsonl", [False, True])
def test_span_lands_on_profiler_host_line(tmp_path, jsonl):
    """While a JAX profile records, a span is a TraceMe under its bare
    name (its args stay out of the name), with or without a JSONL tracer;
    outside it, an untraced span is the shared no-op again."""
    assert T.span("batch_build", cat="build") is T.NOOP
    tracer = T.install(T.Tracer(None)) if jsonl else None
    with jax.profiler.trace(str(tmp_path / "prof")):
        for pos in range(3):
            with T.span("batch_build", cat="build", epoch=0, pos=pos):
                pass
    T.uninstall()
    names = _profile_host_names(tmp_path / "prof")
    assert names.count("batch_build") == 3
    assert not [n for n in names if "pos" in n or "epoch=" in n]
    if jsonl:
        evs = tracer.events()
        assert [e["args"]["pos"] for e in evs] == [0, 1, 2]
    assert T.span("batch_build", cat="build") is T.NOOP


# ---------------------------------------------------------------------------
# analyzer arithmetic on synthetic span sets (times in us)
# ---------------------------------------------------------------------------
def _x(name, cat, ts, dur, tid=1, **args):
    return {"name": name, "cat": cat, "ph": "X", "ts": float(ts),
            "dur": float(dur), "pid": 7, "tid": tid, "args": args}


def test_interval_arithmetic():
    assert R.merge_intervals([(0, 10), (5, 15), (20, 30)]) == \
        [(0, 15), (20, 30)]
    assert R.intersect_total([(0, 10), (20, 30)], [(5, 25)]) == 10.0
    assert R.intersect_total([(0, 10)], [(10, 20)]) == 0.0


def test_overlap_fraction_synthetic():
    evs = [_x("producer_build", "producer", 0, 10, tid=2),
           _x("producer_build", "producer", 20, 10, tid=2),
           _x("train_step", "step", 5, 20, tid=1)]
    ov = R.overlap_fraction(evs)
    # producer busy [0,10]+[20,30]=20us; steps [5,25]; overlap 5+5=10us
    assert ov["producer_busy_s"] == pytest.approx(20 / 1e6)
    assert ov["overlap_s"] == pytest.approx(10 / 1e6)
    assert ov["overlap_frac"] == pytest.approx(0.5)


def test_sync_pipeline_overlap_is_zero_by_construction():
    evs = [_x("train_step", "step", 0, 10),
           _x("epoch_flush", "sync", 10, 2)]
    ov = R.overlap_fraction(evs)
    assert ov["producer_busy_s"] == 0.0 and ov["overlap_frac"] == 0.0


def test_stall_attribution_synthetic():
    evs = [_x("queue_get_wait", "wait", 0, 30),
           _x("queue_get_wait", "wait", 50, 10),
           _x("queue_put_wait", "wait", 60, 40)]
    st = R.stall_attribution(evs)
    assert st["queue_get_wait"]["count"] == 2
    assert st["queue_get_wait"]["total_s"] == pytest.approx(40 / 1e6)
    # wall is [0, 100]
    assert st["queue_put_wait"]["frac_of_wall"] == pytest.approx(0.4)


def test_epoch_rollups_and_mid_epoch_sync_gate():
    evs = [
        _x("epoch", "loop", 0, 100, epoch=0),
        _x("train_step", "step", 0, 20, step=0),
        _x("guard_sync", "sync", 25, 5),        # BEFORE last step: mid
        _x("train_step", "step", 40, 20, step=1),
        _x("cache_refill", "sync", 45, 5),      # inside last step: boundary
        _x("epoch_flush", "sync", 62, 5),       # after last step: boundary
    ]
    eps = R.epoch_rollups(evs)
    assert len(eps) == 1
    ep = eps[0]
    assert ep["epoch"] == 0 and ep["n_steps"] == 2
    assert ep["mid_epoch_syncs"] == 1
    assert ep["mid_epoch_sync_names"] == ["guard_sync"]
    assert ep["spans"]["train_step"]["count"] == 2
    rep = R.analyze(evs)
    assert rep["mid_epoch_sync_count"] == 1
    assert rep["sync_sites"]["epoch_flush"]["count"] == 1


def test_epoch_rollup_empty_epoch_has_no_mid_syncs():
    evs = [_x("epoch", "loop", 0, 10, epoch=4),
           _x("stats_flush", "sync", 2, 1)]
    (ep,) = R.epoch_rollups(evs)
    assert ep["n_steps"] == 0 and ep["mid_epoch_syncs"] == 0


# ---------------------------------------------------------------------------
# per-replica (per-pid) rollups: one Perfetto track per mesh replica
# ---------------------------------------------------------------------------
def _xp(pid, name, cat, ts, dur, **args):
    ev = _x(name, cat, ts, dur, **args)
    ev["pid"] = pid
    return ev


def test_per_pid_gate_fails_on_dirty_rank1_even_when_rank0_clean():
    """Two replica tracks over the same wall window. Rank 0 (pid 7) is
    clean. Rank 1 (pid 1001) has a sync at ts=50 — AFTER rank 0's last
    step start (20), so a pid-blind rollup would call it
    boundary-placed, but BEFORE rank 1's own last step (80): judged per
    pid it is mid-epoch and the gate must fail."""
    evs = [
        # rank 0: both syncs at/after its last step start -> boundary
        _xp(7, "epoch", "loop", 0, 100, epoch=0),
        _xp(7, "train_step", "step", 0, 15, step=0),
        _xp(7, "train_step", "step", 20, 15, step=1),
        _xp(7, "epoch_flush", "sync", 36, 4, epoch=0),
        # rank 1: same epoch envelope, later final step, early sync
        _xp(1001, "epoch", "loop", 0, 100, epoch=0),
        _xp(1001, "train_step", "step", 0, 15, step=0),
        _xp(1001, "halo_wait", "sync", 50, 5),
        _xp(1001, "train_step", "step", 80, 15, step=1),
        _xp(1001, "epoch_flush", "sync", 96, 4, epoch=0),
    ]
    eps = {ep["pid"]: ep for ep in R.epoch_rollups(evs)}
    assert set(eps) == {7, 1001}
    # rank 0 judged against ITS OWN steps only: rank 1's ts=80 step must
    # not drag rank 0's flush (ts=36) into mid-epoch territory...
    assert eps[7]["mid_epoch_syncs"] == 0
    assert eps[7]["n_steps"] == 2
    assert eps[7]["spans"]["train_step"]["count"] == 2  # not 4
    # ...and rank 1's early sync cannot hide behind rank 0's clean track
    assert eps[1001]["mid_epoch_syncs"] == 1
    assert eps[1001]["mid_epoch_sync_names"] == ["halo_wait"]
    rep = R.analyze(evs)
    assert rep["mid_epoch_sync_count"] == 1          # the gate fails
    assert rep["mid_epoch_sync_by_pid"] == {"7": 0, "1001": 1}


def test_replica_trace_emitter_tracks_pass_per_pid_gate():
    """`dist.gnn.ReplicaTraceEmitter` + `Tracer.for_replica` end to end
    on synthetic aux: distinct pid per replica, per-replica loss shares
    on the spans, rollup instants with the halo-bytes model, and every
    replica's reconstructed timeline passes the per-pid gate."""
    from repro.dist import gnn as dist_gnn
    hplan = dist_gnn.HaloPlan("halo", 1, 8)
    em = dist_gnn.ReplicaTraceEmitter(2, hplan, 8, 4)
    aux0 = {"loss": np.array([0.5, 0.25]), "dropped": np.array([0, 3]),
            "hits": np.array([2, 0]), "misses": np.array([1, 4])}
    aux1 = {"loss": np.array([0.4, 0.2]), "dropped": np.array([0, 1]),
            "hits": np.array([5, 0]), "misses": np.array([0, 2])}
    with T.enabled(None) as tr:
        em.note(0.0, 10.0, 0, aux0)
        em.note(20.0, 10.0, 1, aux1)
        em.flush(tr, epoch=0)
        assert em._steps == [] and em._aux == []     # drained
        evs = tr.events()
    pids = {e["pid"] for e in evs}
    assert len(pids) == 2 and tr.pid not in pids
    steps = [e for e in evs if e["name"] == "train_step"]
    assert len(steps) == 4                           # 2 steps x 2 replicas
    by_r = {}
    for e in steps:
        by_r.setdefault(e["args"]["replica"], []).append(e)
    assert by_r[0][0]["args"]["loss_share"] == pytest.approx(0.5)
    assert by_r[1][1]["args"]["loss_share"] == pytest.approx(0.2)
    roll = {e["args"]["replica"]: e["args"] for e in evs
            if e["name"] == "replica_rollup"}
    assert roll[1]["halo_dropped"] == 4
    assert roll[0]["cache_hits"] == 7 and roll[0]["cache_misses"] == 1
    assert roll[0]["halo_bytes"] == 2 * hplan.bytes_per_gather(8, 4, 2)
    # each replica's track is a well-formed epoch that passes the gate
    eps = R.epoch_rollups(evs)
    assert len(eps) == 2
    for ep in eps:
        assert ep["n_steps"] == 2 and ep["mid_epoch_syncs"] == 0
    rep = R.analyze(evs)
    assert rep["mid_epoch_sync_count"] == 0
    assert set(rep["mid_epoch_sync_by_pid"].values()) == {0}


def test_replica_emitter_without_tracer_is_noop():
    from repro.dist import gnn as dist_gnn
    em = dist_gnn.ReplicaTraceEmitter(2, dist_gnn.HaloPlan("halo", 0, 8),
                                      8, 4)
    em.note(0.0, 1.0, 0, {"loss": np.zeros(2), "dropped": np.zeros(2),
                          "hits": np.zeros(2), "misses": np.zeros(2)})
    em.flush(None, epoch=0)                          # no tracer: swallowed
    assert em._steps == []


# ---------------------------------------------------------------------------
# metrics hub
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_primitives():
    h = M.MetricsHub()
    c = h.counter("c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    h.gauge("g").set(2.5)
    assert h.gauge("g").value == 2.5
    hist = h.histogram("h")
    for v in [5.0, 1.0, 3.0, 2.0, 4.0]:
        hist.observe(v)
    s = hist.summary()
    assert s["count"] == 5 and s["min"] == 1.0 and s["max"] == 5.0
    assert s["p50"] == 3.0
    assert hist.percentile(0) == 1.0 and hist.percentile(100) == 5.0
    assert M.Histogram("empty").summary()["count"] == 0


def test_hub_name_bound_to_one_type():
    h = M.MetricsHub()
    h.counter("x")
    with pytest.raises(TypeError):
        h.gauge("x")
    assert h.counter("x") is h.counter("x")     # get-or-create


def test_hub_epoch_marks_are_counter_deltas():
    h = M.MetricsHub()
    h.counter("hits").inc(10)
    h.gauge("rate").set(0.5)
    e0 = h.mark_epoch(0)
    h.counter("hits").inc(3)
    h.gauge("rate").set(0.7)
    e1 = h.mark_epoch(1)
    assert e0["hits"] == 10 and e1["hits"] == 3      # delta, not total
    assert e1["rate"] == 0.7
    assert h.epochs == [e0, e1]
    assert h.snapshot()["hits"] == 13                # totals unharmed


def test_export_schema_and_run_metadata():
    h = M.MetricsHub()
    h.counter("n").inc()
    h.mark_epoch(0)
    out = h.export(extra={"tag": "unit"})
    assert out["schema"] == M.OBS_SCHEMA_VERSION
    assert out["metrics"]["n"] == 1
    assert len(out["epochs"]) == 1
    assert out["tag"] == "unit"
    meta = out["meta"]
    for k in ("schema", "backend", "jax", "git_commit", "hostname",
              "python"):
        assert k in meta, k
    assert meta["backend"] == jax.default_backend()
    json.dumps(out)                                  # JSON-serializable


# ---------------------------------------------------------------------------
# meter absorption: hub mirrors == meter fields, exactly
# ---------------------------------------------------------------------------
def test_hitrate_meter_mirrors_hub():
    h = M.MetricsHub()
    m = HitRateMeter(hub=h)
    m.observe(7, 3)
    m.observe(5, 5)
    m.observe_refill(40)
    m.note_degraded(step=9)
    assert h.counter("cache/hits").value == m.hits == 12
    assert h.counter("cache/misses").value == m.misses == 8
    assert h.counter("cache/refills").value == m.refills == 40
    assert h.counter("cache/degradations").value == 1
    assert h.gauge("cache/hit_rate").value == m.hit_rate


def test_resilience_meter_mirrors_hub():
    h = M.MetricsHub()
    m = ResilienceMeter(hub=h)
    m.note("rollbacks", step=3)
    m.note("skipped_steps", step=1)
    m.note("skipped_steps", step=2)
    assert h.counter("resilience/rollbacks").value == m.rollbacks == 1
    assert h.counter("resilience/skipped_steps").value \
        == m.skipped_steps == 2


def test_straggler_monitor_mirrors_hub_and_windows():
    h = M.MetricsHub()
    m = StragglerMonitor(warmup=2, threshold=2.0, hub=h)
    for _ in range(4):
        m.observe(0.01, 0)              # warmup + 2 normal
    mark = m.mark()
    m.observe(10.0, 4)                  # straggler
    m.observe(0.01, 5)
    assert h.counter("straggler/steps").value == m.count == 6
    assert h.counter("straggler/events").value == len(m.events) == 1
    assert h.gauge("straggler/fraction").value == m.straggler_fraction
    assert h.histogram("straggler/step_time_s").count == 6
    # per-epoch window: 1 straggler of the 2 steps since mark
    assert m.fraction_since(mark) == pytest.approx(0.5)
    assert m.fraction_since(m.mark()) == 0.0


def test_meter_absorption_on_real_trainer_run(tiny_graph):
    """20-step guarded dynamic-cache run: every hub series equals the
    legacy meter's own fields — the absorption is exact, not approximate."""
    tr = soak.make_trainer(tiny_graph, pipeline="sync", ckpt_dir=None,
                           ckpt_every=0)
    tr.train_steps(20)
    hub = tr.hub
    assert hub.counter("cache/hits").value == tr.cache_meter.hits
    assert hub.counter("cache/misses").value == tr.cache_meter.misses
    assert hub.counter("cache/refills").value == tr.cache_meter.refills
    assert hub.gauge("cache/hit_rate").value == tr.cache_meter.hit_rate
    assert hub.counter("straggler/steps").value == tr.straggler.count == 20
    assert hub.gauge("straggler/fraction").value \
        == tr.straggler.straggler_fraction
    for kind, n in tr.guard_meter.counts().items():
        assert hub.counter(f"resilience/{kind}").value == n
    out = hub.export()
    assert out["schema"] == M.OBS_SCHEMA_VERSION
    assert out["metrics"]["cache/hits"] == tr.cache_meter.hits


# ---------------------------------------------------------------------------
# trainer integration: spans, straggler surfacing, bit-exactness
# ---------------------------------------------------------------------------
def test_tracing_on_off_loss_bit_exact(tiny_graph):
    """The zero-cost contract: the traced run's losses are bit-identical
    to the untraced run's (tracing touches no RNG, data, or sync)."""
    tr1 = soak.make_trainer(tiny_graph, pipeline="sync", ckpt_dir=None,
                            ckpt_every=0, guard=None)
    with T.enabled(None):
        traced = tr1.train_steps(6)
    tr2 = soak.make_trainer(tiny_graph, pipeline="sync", ckpt_dir=None,
                            ckpt_every=0, guard=None)
    untraced = tr2.train_steps(6)
    assert traced == untraced           # exact float equality, per step


def test_trainer_emits_expected_span_taxonomy(tiny_graph):
    tr = soak.make_trainer(tiny_graph, pipeline="sync", ckpt_dir=None,
                           ckpt_every=0)
    with T.enabled(None) as tracer:
        d = tr.run_epoch(1e-3)
        evs = tracer.events()
    names = {e["name"] for e in evs}
    assert {"train_step", "epoch", "epoch_flush", "epoch_order",
            "batch_build", "guard_sync", "stats_flush"} <= names
    assert "device_steps" not in names
    # straggler fraction surfaced through the epoch dict
    assert 0.0 <= d["straggler"] <= 1.0
    # the sync stream's build dispatches and epoch order are build spans
    builds = [e for e in evs if e["name"] == "batch_build"]
    n_steps = len([e for e in evs if e["name"] == "train_step"])
    assert {e["cat"] for e in builds} == {"build"}
    assert len(builds) >= n_steps - 1
    (order,) = [e for e in evs if e["name"] == "epoch_order"]
    assert order["cat"] == "build" and order["args"]["epoch"] == 0
    # trainer-side per-epoch snapshot landed in the hub
    assert tr.hub.epochs and tr.hub.epochs[-1]["epoch"] == 0


def test_epoch_metrics_has_straggler_field(tiny_graph):
    from repro.train.gnn_loop import EpochMetrics
    em = EpochMetrics(0, 1.0, 1.0, 0.5, 1.0, 10.0)
    assert em.straggler_fraction == 0.0     # default: no monitor data


def test_checkpoint_spans(tiny_graph, tmp_path):
    from repro.train import checkpoint as ckpt
    tree = {"w": np.arange(4, dtype=np.float32)}
    with T.enabled(None) as tracer:
        ckpt.save(str(tmp_path), 3, tree)
        ckpt.restore(str(tmp_path), 3, tree)
        evs = tracer.events()
    by_name = {e["name"]: e for e in evs}
    assert by_name["ckpt_save"]["cat"] == "sync"
    assert by_name["ckpt_save"]["args"]["step"] == 3
    assert by_name["ckpt_restore"]["cat"] == "ckpt"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def _write_trace(path, events):
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")


def test_cli_report_and_gates(tmp_path, capsys):
    good = [
        _x("epoch", "loop", 0, 100, epoch=0),
        _x("producer_build", "producer", 0, 40, tid=2),
        _x("train_step", "step", 10, 30, step=0),
        _x("epoch_flush", "sync", 45, 5),
    ]
    p = str(tmp_path / "good.jsonl")
    _write_trace(p, good)
    out_json = str(tmp_path / "r.json")
    out_chrome = str(tmp_path / "c.json")
    rc = obs_cli([p, "--json", out_json, "--chrome", out_chrome,
                  "--require-overlap", "--forbid-mid-epoch-sync"])
    assert rc == 0
    rep = json.loads(open(out_json).read())
    assert rep["overlap"]["overlap_frac"] > 0
    assert rep["mid_epoch_sync_count"] == 0
    assert "traceEvents" in json.loads(open(out_chrome).read())

    # no producer spans -> --require-overlap fails
    sync_only = [_x("train_step", "step", 0, 10)]
    p2 = str(tmp_path / "sync.jsonl")
    _write_trace(p2, sync_only)
    assert obs_cli([p2, "--require-overlap"]) == 1
    assert obs_cli([p2]) == 0

    # a mid-epoch sync -> --forbid-mid-epoch-sync fails
    midsync = [
        _x("epoch", "loop", 0, 100, epoch=0),
        _x("train_step", "step", 0, 10, step=0),
        _x("guard_sync", "sync", 15, 2),
        _x("train_step", "step", 30, 10, step=1),
    ]
    p3 = str(tmp_path / "mid.jsonl")
    _write_trace(p3, midsync)
    assert obs_cli([p3, "--forbid-mid-epoch-sync"]) == 1
    assert obs_cli([p3]) == 0
    capsys.readouterr()
