"""Data-parallel GNN training throughput (`repro.dist.gnn`) on a forced
4-device CPU mesh, written to `BENCH_dist.json`.

The driver trains the smoke config on the community-sharded mesh and
reports, per replica and in aggregate:

  batches_per_s           global sharded-step dispatch rate (an SPMD
                          step is ONE dispatch for all replicas)
  roots_per_s_per_replica each replica consumes B/D roots of every
                          global batch: batches_per_s * (B/D)
  straggler_fraction      host dispatch-time outliers
                          (`train.monitor.StragglerMonitor`, the same
                          series the single-device trainer exports)
  halo_plan / halo_bytes  the epoch's planned exchange mode + modeled
                          collective bytes per gather and per epoch
                          (`core.halo.collective_bytes_model`)
  replica_rollups         per-replica loss share / halo drops / cache
                          counters, reconstructed from the sharded
                          step's aux outputs via `ReplicaTraceEmitter`
                          (one Perfetto pid per replica)

plus a `bit_identity` verdict: a 1-replica mesh losses-`==` the
single-device trainer over the probe steps — the determinism headline
of the sharded path, asserted by CI on every run.

The platform comes from the environment, never from this script. The
CPU configuration forces four host devices:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=.:src python -m benchmarks.dist_bench [--smoke]

CPU-simulated mesh numbers are layout/contract validation, not kernel
perf (see the `_meta` note in the artifact).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from benchmarks.common import _REPO_ROOT, dataset, write_bench_json
from repro.configs.base import GNNConfig, TrainConfig
from repro.dist import gnn as dist_gnn
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.train.gnn_loop import GNNTrainer

BENCH_DIST_JSON = os.path.join(_REPO_ROOT, "BENCH_dist.json")


def _cfg(g, smoke: bool):
    return GNNConfig(f"sage-{g.name}", "sage", 2, 16 if smoke else 64,
                     g.feat_dim, g.num_classes, fanout=(5, 5))


def _trainer(g, cfg, tcfg, mesh):
    return GNNTrainer(g, cfg, tcfg, "comm_rand", caps=(512, 1024),
                      eval_caps=(512, 1024), seed=3, mesh=mesh)


def bit_identity_probe(g, cfg, tcfg, steps: int = 8) -> bool:
    """1-replica mesh vs plain single-device: exact `==` on the loss
    trajectory (the tests pin the params digest too; the bench keeps a
    fast standing verdict in the artifact)."""
    a = _trainer(g, cfg, tcfg, None)
    b = _trainer(g, cfg, tcfg, dist_gnn.make_gnn_mesh(1))
    return a.train_steps(steps) == b.train_steps(steps)


def run(smoke: bool) -> dict:
    d = jax.device_count()
    g = dataset("tiny" if smoke else "small")
    cfg = _cfg(g, smoke)
    tcfg = TrainConfig(batch_size=32 if smoke else 256, max_epochs=2)
    mesh = dist_gnn.make_gnn_mesh(d)
    tr = _trainer(g, cfg, tcfg, mesh)
    tr.warmup()

    trace_path = os.path.join(_REPO_ROOT, "benchmarks", "artifacts",
                              "dist_trace.jsonl")
    os.makedirs(os.path.dirname(trace_path), exist_ok=True)
    if os.path.exists(trace_path):
        os.remove(trace_path)
    with obs_trace.enabled(trace_path, run="dist_bench") as tracer:
        t0 = time.perf_counter()
        em = tr.run_epoch(tcfg.learning_rate)
        wall = time.perf_counter() - t0
        tracer.flush()
    n_batches = tr.stream.num_batches()
    hplan = tr._hplan
    bytes_per_gather = hplan.bytes_per_gather(tr.caps[-1], g.feat_dim, d)

    evs = obs_report.load_trace(trace_path)
    rollups = [ev["args"] for ev in evs if ev["name"] == "replica_rollup"]
    per_pid = obs_report.analyze(evs)["mid_epoch_sync_by_pid"]

    return {
        "dataset": g.name,
        "n_replicas": d,
        "batch_size": tcfg.batch_size,
        "n_batches": n_batches,
        "epoch_loss": em["loss"],
        "batches_per_s": n_batches / max(em["time"], 1e-9),
        "roots_per_s_per_replica":
            n_batches / max(em["time"], 1e-9) * (tcfg.batch_size / d),
        "straggler_fraction": em["straggler"],
        "wall_s": wall,
        "halo_plan": {"mode": hplan.mode, "halo": hplan.halo,
                      "r_cap": hplan.r_cap},
        "halo_bytes_per_gather": bytes_per_gather,
        "halo_bytes_per_epoch": bytes_per_gather * n_batches,
        "replica_rollups": rollups,
        "mid_epoch_sync_by_pid": per_pid,
        "mid_epoch_syncs_total": sum(per_pid.values()),
        "bit_identity": bit_identity_probe(g, cfg, tcfg),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph, 2 epochs — the CI configuration")
    args = ap.parse_args()
    rep = run(smoke=args.smoke)
    assert rep["n_replicas"] == 4, (
        "dist bench expects a 4-device mesh; got "
        f"{rep['n_replicas']} (on CPU, set XLA_FLAGS="
        "--xla_force_host_platform_device_count=4)")
    write_bench_json({"dist/gnn": rep}, path=BENCH_DIST_JSON)
    print(json.dumps(rep, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
