"""Finds what a benchmark cell is made of, by name, in the file layout.

    BENCHMARK.json                  cells, metrics and bounds
    chipbench/configs/<config>.json model sizes and optimizer; names its
                                    model and its graph
    chipbench/graphs/<graph>.json   a published graph's shape and the
                                    generator's assumed knobs
    chipbench/traffic/<traffic>.json batch policy and its parameters
    chipbench/limits/<cell>.json    the limits `correct` is decided on
    chipbench/reference/<model>.py  the model's plain reference
    chipbench/flops/<model>.py      the model's operation counts
    chipbench/rooflines/<kernel>.py a kernel's operation and byte counts
    chipbench/metrics/<metric>.py   the reader of one per-layer metric

A new configuration, graph, traffic mix, model or metric is a new file
here (and an entry in BENCHMARK.json); no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

from chipbench.graphgen import GraphSpec


class Layout:
    def __init__(self, root):
        self.root = Path(root)
        self.dir = self.root / "chipbench"
        with open(self.root / "BENCHMARK.json") as f:
            self.bench = json.load(f)
        self._modules = {}

    def _json(self, *parts):
        with open(self.dir.joinpath(*parts)) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", f"{name}.json")

    def graph_spec(self, name: str) -> GraphSpec:
        return GraphSpec.from_dict(name, self._json("graphs", f"{name}.json"))

    def traffic(self, name: str) -> dict:
        return self._json("traffic", f"{name}.json")

    def limits(self, workload: str) -> dict:
        return self._json("limits", f"{workload}.json")

    def peaks(self, device_kind: str) -> dict:
        table = self._json("peaks.json")
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"chipbench/peaks.json")
        return table[device_kind]

    def metrics(self, kind: str, workload: str) -> list:
        """The `end_to_end` or `per_layer` entries this workload reports."""
        return [m for m in self.bench[kind]
                if workload in m.get("workloads", [workload])]

    def module(self, kind: str, name: str):
        """`chipbench/<kind>/<name>.py`, loaded from this layout."""
        path = self.dir / kind / f"{name}.py"
        if path not in self._modules:
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{kind}_{name}", path)
            if spec is None or not path.exists():
                raise KeyError(f"no {kind} module {name!r} ({path})")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]
