"""Quickstart: COMM-RAND vs uniform-random mini-batching in ~60 seconds.

Generates a community-structured synthetic graph, preprocesses it
(community detection -> RABBIT-style reorder -> intra-first rows), then
trains GraphSAGE under two `repro.batching` policies and prints the
paper's four metrics.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.batching import available_policies, make_policy
from repro.configs.base import GNNConfig, TrainConfig
from repro.core.reorder import prepare
from repro.graphs import synthetic
from repro.runtime import use_compile_cache
from repro.train.gnn_loop import train_once


def main():
    use_compile_cache()
    print("== generating community-structured graph (tiny SBM) ==")
    g = prepare(synthetic.load("tiny"), oracle=False)   # runs Louvain
    print(f"graph: {g.num_nodes} nodes, {g.num_edges} edges, "
          f"{g.communities.max() + 1} detected communities")
    print(f"registered batch policies: {', '.join(available_policies())}")

    cfg = GNNConfig("sage-quickstart", "sage", 2, 64, g.feat_dim,
                    g.num_classes, fanout=(10, 10))
    tcfg = TrainConfig(batch_size=512, max_epochs=15)

    rows = []
    for pol in (make_policy("rand"),                      # baseline
                make_policy("comm_rand", mix=0.125, p=1.0)):  # paper §6.1.3
        r = train_once(g, cfg, pol, tcfg, seed=0)
        rows.append(r)
        print(f"{r.policy:28s} val_acc={r.val_acc:.4f} "
              f"epochs={r.epochs_to_converge} "
              f"per_epoch={r.per_epoch_time_s * 1e3:.0f}ms "
              f"unique_nodes/batch={r.mean_unique_nodes:.0f}")
    base, best = rows
    print(f"\nCOMM-RAND: {base.per_epoch_time_s / best.per_epoch_time_s:.2f}x"
          f" per-epoch speedup, "
          f"{base.mean_unique_nodes / best.mean_unique_nodes:.2f}x smaller"
          f" working set, val acc delta "
          f"{(base.val_acc - best.val_acc) * 100:+.2f}pp")


if __name__ == "__main__":
    main()
