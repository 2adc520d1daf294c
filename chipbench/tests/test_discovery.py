"""A graph, a configuration, a traffic mix, a cell and a per-layer metric
added as new files only (plus entries in BENCHMARK.json), in a copy of
the layout, are found and run by the unchanged harness."""
import json

import pytest

from chipbench import run, trace_reduce
from chipbench.layout import Layout
from tiny import TINY_GRAPH, make_layout, tiny_config, write_json

NEW_METRIC = '''
def read(ctx):
    return float(ctx.steps)
'''


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    root = make_layout(tmp_path_factory.mktemp("discovery"))
    cb = root / "chipbench"
    before = {p: p.read_bytes() for p in cb.rglob("*") if p.is_file()}
    write_json(cb / "graphs" / "small2.json",
               {**TINY_GRAPH, "num_nodes": 2500, "num_edges": 30000,
                "split": [1500, 400, 600]})
    write_json(cb / "configs" / "sage-small2.json",
               {**tiny_config("sage"), "graph": "small2",
                "hidden_dim": 24})
    write_json(cb / "traffic" / "commrand25.json",
               {"policy": "comm_rand", "mix": 0.25, "p": 0.9})
    (cb / "metrics" / "steps_seen.py").write_text(NEW_METRIC)
    write_json(cb / "limits" / "sage-small2-commrand25.json",
               json.loads((cb / "limits" /
                           "sage-reddit-commrand.json").read_text()))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "sage-small2-commrand25", "config": "sage-small2",
         "traffic": "commrand25", "chips": 1, "why": "test"})
    bench["per_layer"].append(
        {"name": "steps_seen", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "device",
         "moves": "train_nodes_per_s",
         "workloads": ["sage-small2-commrand25"]})
    write_json(root / "BENCHMARK.json", bench)
    after = {p: p.read_bytes() for p in cb.rglob("*") if p.is_file()}
    assert all(after[p] == b for p, b in before.items())  # nothing edited
    return Layout(root)


def test_files_are_found(layout):
    cell = layout.workload("sage-small2-commrand25")
    cfg = layout.config(cell["config"])
    assert layout.graph_spec(cfg["graph"]).num_nodes == 2500
    assert layout.traffic(cell["traffic"])["mix"] == 0.25
    names = [m["name"] for m in
             layout.metrics("per_layer", "sage-small2-commrand25")]
    assert "steps_seen" in names
    assert "steps_seen" not in [
        m["name"] for m in layout.metrics("per_layer",
                                          "sage-reddit-commrand")]


def test_new_cell_runs(layout, monkeypatch, capsys):
    # the CPU stands in for the chip: its ops are on the host's PjRt
    # thread, and its peaks are the v5e's
    orig = trace_reduce.devices
    monkeypatch.setattr(trace_reduce, "devices", lambda pd: orig(
        pd, plane_match=lambda n: n == "/host:CPU",
        ops_line=lambda n: n.startswith("tf_XLAPjRtCpuClient")))
    monkeypatch.setattr(Layout, "peaks",
                        lambda self, kind: {"bf16_flops_per_s": 197e12,
                                            "hbm_bytes_per_s": 819e9})
    rc = run.main(["--workload", "sage-small2-commrand25", "--seed",
                   str(2 ** 31 + 11), "--seconds", "0.5", "--trace", "1"],
                  require_chip=False, root=layout.root)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["steps_seen"]["value"] == result["attempted"]
    assert result["metrics"]["steps_seen"]["unit"] == "steps"
    assert list(result)[-1] == "compared"
