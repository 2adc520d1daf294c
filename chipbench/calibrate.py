#!/usr/bin/env python3
"""Readings that a cell's limits are set from (see PERF.md).

    python3 chipbench/calibrate.py --workload <cell> --seeds 1-12 \
        --faults 3 [--out readings.json]

For every seed: the program's first three steps and batches, set up as
a run sets them up, against the reference (the sound readings). For the
first `--faults` seeds also, against the same reference:

  control     the reference computed in bfloat16 in the program's place
  half_batch  the reference with half of each batch's roots left out, the
              mean taken over the rest
  state_kept  a step that returns its state unchanged (the first
              gradient and the parameters' change read 0)
  batch_alt   the program's batches with one sampled neighbor altered

Each line of output is one JSON object; `--out` also gets them all.
Needs the chip a cell runs on, like `run.py`.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def seed_list(spec: str) -> list:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def altered(batches: list) -> list:
    """One sampled neighbor of the first batch's last hop pointed at
    another row of its source level."""
    out = copy.deepcopy(batches)
    hop = out[0]["hops"][-1]
    i, j = [int(x[0]) for x in hop["edge_mask"].nonzero()]
    n = len(out[0]["levels"][-1])
    hop["src_pos"][i, j] = (hop["src_pos"][i, j] + 1) % n
    return out


def readings(sess, faults: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.reference.common import compare

    from chipbench.reference.common import leaf_gaps

    ref = sess.reference()
    out = {"program": sess.compared(ref=ref),
           "alt_taken": ref["alt_taken"],
           "losses": {"program": sess.first["losses"],
                      "reference": ref["losses"]},
           "leaf_gaps": leaf_gaps(sess.first, ref, sess.params0)}
    if faults:
        p0 = sess.params0
        out["control"] = compare(sess.reference(dtype=jnp.bfloat16), ref, p0)
        out["half_batch"] = compare(sess.reference(half_batch=True), ref, p0)
        zeros = jax.tree.map(np.zeros_like, ref["grad1"])
        out["state_kept"] = compare(
            {"losses": sess.first["losses"], "grad1": zeros,
             "params3": p0}, ref, p0)
        out["batch_alt"] = {"batch_faults":
                            sess.batch_faults(altered(sess.batches))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--faults", type=int, default=3,
                    help="run the control and faults on this many seeds")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    from chipbench import dataset, graphgen
    from chipbench.layout import Layout
    from chipbench.run import Session, device_info

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"calibrate: no TPU ({dev})", file=sys.stderr)
        return 3
    layout = Layout(ROOT)
    jax.config.update("jax_compilation_cache_dir",
                      str(layout.dir / ".cache" / "jax"))
    cell = layout.workload(args.workload)
    spec = layout.graph_spec(layout.config(cell["config"])["graph"])
    graph = dataset.prepared_graph(spec, layout.dir / ".cache")
    graph.features = graphgen.features(spec, graph.labels,
                                       graph.communities)
    lines = []
    for k, seed in enumerate(seed_list(args.seeds)):
        t0 = time.perf_counter()
        sess = Session(layout, args.workload, seed, graph=graph)
        sess.free_program()
        rec = {"workload": args.workload, "seed": seed,
               "caps": list(sess.caps),
               **readings(sess, k < args.faults),
               "seconds": time.perf_counter() - t0, "device": dev}
        print(json.dumps(rec), flush=True)
        lines.append(rec)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            for rec in lines:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        ROOT / "chipbench" / ".cache" / "jax")
    sys.exit(main())
