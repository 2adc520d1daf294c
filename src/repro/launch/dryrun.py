"""LM sharding dry-run: lower and compile every (arch, shape) cell on the
production mesh of 512 host devices, and record memory, cost and
collective bytes.

The platform comes from the environment, set before Python starts:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=512 \
        PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b

`make_production_mesh` refuses to run on fewer devices.
"""
import argparse
import json
import os
import re
import time
import traceback

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (SHAPES, TrainConfig, long_context_ok)
from repro.configs.registry import LM_ARCHS, get_config
from repro.dist import sharding as shd
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.models.lm import transformer
from repro.optim import adamw
from repro.train.train_step import (make_decode_step, make_prefill_step,
                                    make_train_step)

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")

_COLL_RE = re.compile(
    r"=\s+(\S+?)\[([0-9,]*)\]\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP1D_RE = re.compile(r"replica_groups=\[(\d+)\]<=")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8, "c64": 8, "c128": 16}


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w\.\-]+)\s*\(")
_WHILE_RE = re.compile(
    r"while\(.*?\), condition=%?([\w\.\-]+), body=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _loop_multipliers(hlo_text: str):
    """Map computation-name -> execution multiplier, accounting for nested
    `while` loops (XLA cost analysis counts loop bodies ONCE; jax scans
    lower to while loops whose trip count appears as the constant in the
    loop condition)."""
    comp_of = {}          # comp name -> list of its lines
    cur = None
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _COMP_RE.match(s)
        if m and s.endswith("{"):
            cur = m.group(1)
            comp_of[cur] = []
        elif cur is not None:
            comp_of[cur].append(line)

    def cond_trip(cond_lines):
        """Trip count = the constant operand of the ROOT compare (taking a
        max over all constants grabs unrelated bounds)."""
        const_of = {}
        for cl in cond_lines:
            mm = re.search(r"%([\w\.\-]+) = s32\[\]\{?:?\S*\}? ?constant\((\d+)\)", cl)
            if mm:
                const_of[mm.group(1)] = int(mm.group(2))
        for cl in cond_lines:
            if "ROOT" in cl and "compare(" in cl:
                for o in re.findall(r"%([\w\.\-]+)", cl):
                    if o in const_of:
                        return const_of[o]
        # fallback: XLA may inline the bound via a known_trip_count config
        return 1

    trip_of_body = {}
    parent_of_body = {}
    for comp, lines in comp_of.items():
        for line in lines:
            w = _WHILE_RE.search(line)
            if not w:
                continue
            cond, body = w.group(1), w.group(2)
            trip = cond_trip(comp_of.get(cond, []))
            tc = re.search(r'known_trip_count[":{]+n[":]+(\d+)', line)
            if tc:
                trip = int(tc.group(1))
            trip_of_body[body] = trip
            parent_of_body[body] = comp

    def mult(comp, depth=0):
        if depth > 16 or comp not in trip_of_body:
            return 1.0
        return trip_of_body[comp] * mult(parent_of_body.get(comp, ""),
                                         depth + 1)

    return comp_of, {c: mult(c) for c in comp_of}


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective traffic from the partitioned HLO, with nested
    while-loop trip-count multipliers (FSDP weight all-gathers live inside
    the layer scan and execute L times; counting them once underestimates
    the collective term by ~L).

    Shapes in the SPMD module are per-device local. Model (ring):
      all-gather          -> result_bytes        (received)
      all-reduce          -> 2 * operand_bytes   (reduce-scatter + all-gather)
      reduce-scatter      -> result_bytes * group (operand sent)
      all-to-all/permute  -> result_bytes
    """
    comp_of, mults = _loop_multipliers(hlo_text)
    per_op = {}
    total = 0.0
    for comp, lines in comp_of.items():
        mult = mults.get(comp, 1.0)
        for line in lines:
            m = _COLL_RE.search(line)
            if m is None:
                continue
            dtype, dims, op = m.group(1), m.group(2), m.group(3)
            nbytes = _DTYPE_BYTES.get(dtype, 4)
            for d in dims.split(","):
                if d:
                    nbytes *= int(d)
            g = _GROUP_RE.search(line)
            if g:
                group = int(g.group(2))
            else:
                g1 = _GROUP1D_RE.search(line)
                group = int(g1.group(1)) if g1 else 16
            if op == "all-reduce":
                b = 2.0 * nbytes
            elif op == "reduce-scatter":
                b = float(nbytes) * group
            else:
                b = float(nbytes)
            per_op[op] = per_op.get(op, 0.0) + b * mult
            total += b * mult
    per_op["total"] = total
    return per_op


def _sds_with(tree, spec_tree, mesh):
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        tree, spec_tree)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               tcfg: TrainConfig = None):
    """Lower + compile one (arch x shape x mesh) cell.

    Returns (compiled, lowered, meta) — raises on any sharding/compile bug.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not long_context_ok(cfg):
        return None, None, {"skipped": "full-attention arch: long_500k needs "
                                       "sub-quadratic attention (DESIGN.md §5)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    tcfg = tcfg or TrainConfig()
    max_seq = 32768 if cfg.learned_pos else 4096
    aparams = transformer.abstract_params(cfg, max_seq=max_seq)
    pspec = shd.param_specs(aparams, mesh)
    params_in = _sds_with(aparams, pspec, mesh)

    t0 = time.time()
    if shape.kind == "train":
        step, _ = make_train_step(cfg, tcfg, mesh)
        aopt = jax.eval_shape(adamw.init, aparams)
        ospec = {"m": pspec, "v": pspec, "count": P()}
        opt_in = _sds_with(aopt, ospec, mesh)
        batch = specs_mod.train_batch_specs(cfg, shape.global_batch,
                                            shape.seq_len)
        batch_in = _sds_with(batch, shd.batch_specs(batch, mesh, "fsdp"),
                             mesh)
        lowered = step.lower(params_in, opt_in, batch_in)
    elif shape.kind == "prefill":
        batch = specs_mod.prefill_batch_specs(cfg, shape.global_batch,
                                              shape.seq_len)
        batch_in = _sds_with(batch, shd.batch_specs(batch, mesh, "tp_sp"),
                             mesh)
        acache = jax.eval_shape(
            lambda: transformer.init_cache(cfg, shape.global_batch,
                                           shape.seq_len, jnp.bfloat16))
        cspec = shd.cache_specs(acache, mesh)
        # jit with cache out_shardings so the cache is not replicated
        from repro.train.train_step import _with_mesh_ctx
        fn = _with_mesh_ctx(mesh, lambda p, b: transformer.prefill(cfg, p, b),
                            "tp_sp")
        logits_spec = P(shd.ShardCtx(mesh, "tp_sp").batch_axes, None,
                        "model")
        step = jax.jit(fn, out_shardings=(
            NamedSharding(mesh, logits_spec),
            shd.to_shardings(cspec, mesh)))
        lowered = step.lower(params_in, batch_in)
    else:  # decode
        acache, tok_s, pos_s = specs_mod.decode_specs(
            cfg, shape.global_batch, shape.seq_len)
        cspec = shd.cache_specs(acache, mesh)
        cache_in = _sds_with(acache, cspec, mesh)
        tok_in = _sds_with(tok_s, shd.batch_specs(tok_s, mesh, "tp_sp"),
                           mesh)
        pos_in = jax.ShapeDtypeStruct((), jnp.int32,
                                      sharding=NamedSharding(mesh, P()))
        from repro.train.train_step import _with_mesh_ctx
        fn = _with_mesh_ctx(
            mesh, lambda p, c, t, i: transformer.decode_step(cfg, p, c, t, i),
            "tp_sp")
        logits_spec = P(shd.ShardCtx(mesh, "tp_sp").batch_axes
                        if shape.global_batch % 32 == 0 else None,
                        None, "model")
        step = jax.jit(fn, donate_argnums=(1,), out_shardings=(
            NamedSharding(mesh, logits_spec),
            shd.to_shardings(cspec, mesh)))
        lowered = step.lower(params_in, cache_in, tok_in, pos_in)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    meta = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_per_device": cost.get("bytes accessed", 0.0),
        "collective_bytes_per_device": coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "param_count": int(sum(
            int(np.prod(np.asarray(x.shape, dtype=np.int64)))
            for x in jax.tree.leaves(aparams))),
    }
    return compiled, lowered, meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=ART_DIR)
    args = ap.parse_args()

    archs = list(LM_ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
                try:
                    compiled, lowered, meta = lower_cell(
                        arch, shape, multi_pod=mp)
                    if compiled is None:
                        print(f"SKIP {tag}: {meta['skipped']}")
                    else:
                        mem = meta["memory"]
                        per_dev_gib = (mem["argument_bytes"] +
                                       mem["temp_bytes"]) / 2**30
                        print(f"OK   {tag}: compile={meta['t_compile_s']}s "
                              f"flops/dev={meta['flops_per_device']:.3e} "
                              f"mem/dev={per_dev_gib:.2f}GiB "
                              f"coll/dev={meta['collective_bytes_per_device']['total']:.3e}B")
                    path = os.path.join(args.out, tag + ".json")
                    with open(path, "w") as f:
                        json.dump(meta, f, indent=1)
                    del compiled, lowered
                except Exception as e:  # noqa: BLE001 — report, keep going
                    traceback.print_exc()
                    print(f"FAIL {tag}: {type(e).__name__}: {str(e)[:300]}")
                    failures.append(tag)
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
