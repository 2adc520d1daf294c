"""Pod-scale GNN dry-run: the paper's pipeline at ogbn-papers100M scale.

Topology stays on hosts (DGL-style CPU sampling; DESIGN.md §4) — the device
step consumes prebuilt MiniBatch index towers. The feature table (111M x 128
fp32) is sharded into community-contiguous ranges over a `shard` axis; the
batch feature gather runs through `core.halo` (halo budget for COMM-RAND,
global fallback for RAND), then the SAGE tower + AdamW.

Static caps per policy come from calibration on the papers-like synthetic
graph (see EXPERIMENTS.md §Dry-run), scaled to papers100M's fanout tree.

Needs 512 host devices, set in the environment before Python starts:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=512 \
        PYTHONPATH=src python -m repro.launch.dryrun_gnn
"""
import argparse
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import GNNConfig, TrainConfig
from repro.core import halo as halo_mod
from repro.core.minibatch import Block, MiniBatch
from repro.launch.dryrun import ART_DIR, collective_bytes
from repro.models.gnn.models import apply_gnn, init_gnn
from repro.optim import adamw
from repro.train.losses import gnn_softmax_ce


N_NODES = 111_059_956
FEAT_DIM = 128
NUM_CLASSES = 172
ROOTS_PER_DEV = 1024
FANOUTS = (10, 10, 10)

# calibrated caps (unique nodes per level, per device batch)
POLICY_CELLS = {
    # policy          caps                              r_cap   halo  mode
    "rand_p05":   ((1024, 11264, 109568, 875520),       0,      0,  "global"),
    "commrand_mix125_p10": ((1024, 8192, 24576, 53248), 8192,   2,  "halo"),
    "norand_p10": ((1024, 6144, 16384, 32768),          4096,   1,  "halo"),
    # §Perf hillclimb: tighter halo budget from p99.5 (vs max) calibration —
    # trades <0.5% dropped halo rows for a 2.4x smaller exchange
    "commrand_mix125_p10_tuned": ((1024, 8192, 24576, 53248), 3456, 2,
                                  "halo"),
}


def gnn_mesh(multi_pod: bool):
    devs = jax.devices()
    if len(devs) < 512:
        raise RuntimeError(f"the dry-run needs 512 devices, found "
                           f"{len(devs)} (see the module docstring)")
    if multi_pod:
        return Mesh(np.asarray(devs[:512]).reshape(2, 256), ("pod", "shard"))
    return Mesh(np.asarray(devs[:256]).reshape(256,), ("shard",))


def batch_specs(caps, n_dev_total):
    """Per-DEVICE MiniBatch tower specs, with a leading device-batch dim that
    shards over ('pod','shard')."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct((n_dev_total,) + shape, dtype)

    levels = [sds((c,), jnp.int32) for c in (ROOTS_PER_DEV,) + caps]
    blocks = []
    dims = (ROOTS_PER_DEV,) + caps
    for h, r in enumerate(FANOUTS):
        blocks.append(Block(
            src_pos=sds((dims[h], r), jnp.int32),
            self_pos=sds((dims[h],), jnp.int32),
            edge_mask=sds((dims[h], r), jnp.bool_),
            dst_mask=sds((dims[h],), jnp.bool_),
        ))
    return MiniBatch(
        levels=levels,
        node_mask=sds((caps[-1],), jnp.bool_),
        blocks=blocks[::-1],
        labels=sds((ROOTS_PER_DEV,), jnp.int32),
        label_mask=sds((ROOTS_PER_DEV,), jnp.bool_),
    )


def lower_gnn_cell(policy_name: str, multi_pod: bool = False):
    caps, r_cap, halo_w, mode = POLICY_CELLS[policy_name]
    mesh = gnn_mesh(multi_pod)
    n_shard = mesh.shape["shard"]
    n_pod = mesh.shape.get("pod", 1)
    n_dev_total = n_shard * n_pod
    n_per_shard = (N_NODES + n_shard - 1) // n_shard
    n_pad = n_per_shard * n_shard

    cfg = GNNConfig("graphsage-papers100m", "sage", 3, 256, FEAT_DIM,
                    NUM_CLASSES, fanout=FANOUTS)
    tcfg = TrainConfig()
    aparams = jax.eval_shape(lambda k: init_gnn(cfg, k), jax.random.key(0))
    aopt = jax.eval_shape(adamw.init, aparams)

    feat_sharding = NamedSharding(mesh, P("shard", None))
    feats_in = jax.ShapeDtypeStruct((n_pad, FEAT_DIM), jnp.float32,
                                    sharding=feat_sharding)
    batch_axes = ("pod", "shard") if multi_pod else ("shard",)
    abatch = batch_specs(caps, n_dev_total)
    batch_in = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=NamedSharding(mesh, P(batch_axes,
                                           *([None] * (len(s.shape) - 1))))),
        abatch)
    repl = NamedSharding(mesh, P())
    params_in = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl),
        aparams)
    opt_in = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl),
        aopt)

    in_specs_gather = (P("shard", None), P(batch_axes, None))
    out_specs_gather = (P(batch_axes, None, None), P(batch_axes))

    @jax.shard_map(mesh=mesh, in_specs=in_specs_gather,
                   out_specs=out_specs_gather, check_vma=False)
    def gather(feats_local, ids_b):
        x, dropped = halo_mod.gather_for_policy(
            feats_local, ids_b[0], n_per_shard=n_per_shard, r_cap=r_cap,
            halo=halo_w, axis="shard", mode=mode)
        return x[None], dropped[None]

    def train_step(params, opt_state, feats, batch: MiniBatch):
        def loss_fn(p):
            x, dropped = gather(feats, batch.node_ids)
            # per-device tower, batched over the device dim via vmap
            logits = jax.vmap(
                lambda bt, xd: apply_gnn(cfg, p, bt, xd, None))(
                batch, x.reshape(n_dev_total, caps[-1], FEAT_DIM))
            loss = jnp.mean(jax.vmap(
                lambda lg, lb, m: gnn_softmax_ce(lg, lb, m))(
                logits, batch.labels,
                batch.label_mask.astype(jnp.float32)))
            return loss, dropped.sum()

        (loss, dropped), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params2, opt2 = adamw.update(grads, opt_state, params,
                                     lr=tcfg.learning_rate,
                                     weight_decay=tcfg.weight_decay)
        return params2, opt2, {"loss": loss, "dropped": dropped}

    step = jax.jit(train_step, donate_argnums=(0, 1),
                   out_shardings=(jax.tree.map(lambda _: repl, aparams),
                                  jax.tree.map(lambda _: repl, aopt),
                                  {"loss": repl, "dropped": repl}))
    lowered = step.lower(params_in, opt_in, feats_in, batch_in)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    meta = {
        "arch": "graphsage-papers100m", "shape": policy_name,
        "mesh": "2x256" if multi_pod else "256", "kind": "gnn-train",
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_per_device": cost.get("bytes accessed", 0.0),
        "collective_bytes_per_device": coll,
        "memory": {"argument_bytes": mem.argument_size_in_bytes,
                   "output_bytes": mem.output_size_in_bytes,
                   "temp_bytes": mem.temp_size_in_bytes,
                   "alias_bytes": mem.alias_size_in_bytes},
        "caps": caps, "r_cap": r_cap, "halo": halo_w, "gather_mode": mode,
        "halo_bytes_model": halo_mod.collective_bytes_model(
            caps[-1], FEAT_DIM, n_shard, r_cap, halo_w, mode),
    }
    return compiled, lowered, meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=ART_DIR)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    fails = []
    for mp in meshes:
        for pol in POLICY_CELLS:
            tag = f"gnn_{pol}_{'2x256' if mp else '256'}"
            try:
                compiled, lowered, meta = lower_gnn_cell(pol, mp)
                per_dev = (meta["memory"]["argument_bytes"] +
                           meta["memory"]["temp_bytes"]) / 2**30
                print(f"OK   {tag}: mem/dev={per_dev:.2f}GiB "
                      f"coll/dev={meta['collective_bytes_per_device']['total']:.3e}B "
                      f"flops/dev={meta['flops_per_device']:.3e}")
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(meta, f, indent=1)
                del compiled, lowered
            except Exception as e:  # noqa: BLE001
                import traceback
                traceback.print_exc()
                fails.append(tag)
    if fails:
        raise SystemExit(f"FAILURES: {fails}")
    print("gnn dry-run cells passed")


if __name__ == "__main__":
    main()
