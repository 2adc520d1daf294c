"""A copy of the benchmark's layout with tiny cells, for tests on the CPU.

`make_layout(tmp)` copies BENCHMARK.json and `chipbench/` (without its
caches and tests) under `tmp`, adds a 3,000-node graph, a SAGE and a GAT
configuration at small widths, and one cell per (model, traffic) pair.
The tiny cells' limits are those of `sage-reddit-commrand`, the limits
the chip cells are held to.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_GRAPH = {
    "source": "test graph", "num_nodes": 3000, "num_edges": 48000,
    "feat_dim": 16, "num_classes": 5, "split": [1800, 300, 900],
    "assumed": {"num_communities": 12, "p_intra": 0.9,
                "community_size_skew": 0, "degree_pareto_a": 2.0,
                "label_noise": 0.1, "feat_noise": 1.0, "seed": 7}}


def tiny_config(model: str) -> dict:
    cfg = {"source": "test", "model": model, "graph": "tiny",
           "num_layers": 3, "hidden_dim": 32, "fanout": [5, 5, 5],
           "batch_size": 64, "learning_rate": 0.001,
           "weight_decay": 0.0005, "dropout": 0.5, "dtype": "float32",
           "matmul_precision": "default"}
    if model == "gat":
        cfg["heads"] = 4
    return cfg


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_layout(tmp: Path) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "tests",
                                                  "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    limits = json.loads(
        (REPO / "chipbench" / "limits" /
         "sage-reddit-commrand.json").read_text())
    write_json(root / "chipbench" / "graphs" / "tiny.json", TINY_GRAPH)
    for model in ("sage", "gat"):
        write_json(root / "chipbench" / "configs" / f"{model}-tiny.json",
                   tiny_config(model))
        for traffic in ("commrand", "rand"):
            name = f"{model}-tiny-{traffic}"
            bench["workloads"].append(
                {"name": name, "config": f"{model}-tiny",
                 "traffic": traffic, "chips": 1, "why": "test"})
            for m in bench["per_layer"]:
                m["workloads"].append(name)
            write_json(root / "chipbench" / "limits" / f"{name}.json",
                       limits)
    write_json(root / "BENCHMARK.json", bench)
    return root
