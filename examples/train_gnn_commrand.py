"""End-to-end driver: train GraphSAGE with COMM-RAND for a few hundred
steps on a reddit-like synthetic graph, with checkpointing + early stopping
— the paper's training pipeline as a user would run it.

Batch construction is all `repro.batching`: the policy comes from the
registry, caps from the cached `CapsCalibrator`, and batches from the
trainer's resumable `BatchStream` — rerun with the same --ckpt-dir after an
interruption and training continues bit-exactly from the saved cursor.

    PYTHONPATH=src python examples/train_gnn_commrand.py \
        --dataset reddit-like --policy comm_rand --mix 0.125 --p 1.0
"""
import argparse

from repro.batching import CapsCalibrator, make_policy
from repro.configs.base import GNNConfig, TrainConfig
from repro.core.reorder import prepare
from repro.graphs import synthetic
from repro.runtime import use_compile_cache
from repro.train.gnn_loop import GNNTrainer


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="reddit-like",
                    choices=sorted(synthetic.DATASETS))
    ap.add_argument("--policy", default="comm_rand",
                    choices=["rand", "norand", "comm_rand"])
    ap.add_argument("--mix", type=float, default=0.125)
    ap.add_argument("--p", type=float, default=1.0)
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--oracle-communities", action="store_true")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint + resume (cursor travels with weights)")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="steps between checkpoints (with --ckpt-dir)")
    ap.add_argument("--caps-cache", default=None,
                    help="JSON file memoizing calibrated caps across runs")
    ap.add_argument("--cache", default=None,
                    choices=["degree_hot", "community_freq",
                             "presampled_freq", "dynamic",
                             "dynamic:degree_hot", "dynamic:community_freq",
                             "dynamic:presampled_freq"],
                    help="device-resident feature cache (repro.featcache): "
                         "a static admission policy, or 'dynamic[:seed-"
                         "admission]' for the on-device CLOCK loop that "
                         "re-admits at every epoch boundary — hit rates "
                         "and refill churn print per epoch")
    ap.add_argument("--cache-frac", type=float, default=0.2,
                    help="cache capacity as a fraction of N (with --cache)")
    ap.add_argument("--pipeline", default="sync",
                    choices=["sync", "async"],
                    help="batch pipeline: 'sync' = classic BatchStream; "
                         "'async' = repro.pipeline's depth-2 background "
                         "prefetcher over the fused on-device builder "
                         "(bit-exact same batches, overlapped with the "
                         "train step)")
    args = ap.parse_args()

    g = prepare(synthetic.load(args.dataset),
                oracle=args.oracle_communities)
    pol = make_policy(args.policy, mix=args.mix, p=args.p)
    cfg = GNNConfig(f"sage-{args.dataset}", "sage", args.layers, args.hidden,
                    g.feat_dim, g.num_classes,
                    fanout=(10,) * args.layers)
    tcfg = TrainConfig(batch_size=args.batch_size, max_epochs=args.epochs)
    print(f"policy: {pol.describe()}  graph: {g.name} ({g.num_nodes} nodes)")
    tr = GNNTrainer(g, cfg, tcfg, pol, seed=0, ckpt_dir=args.ckpt_dir,
                    ckpt_every=args.ckpt_every,
                    calibrator=CapsCalibrator(cache_path=args.caps_cache),
                    cache=args.cache, cache_frac=args.cache_frac,
                    pipeline=args.pipeline).warmup()
    print(f"calibrated caps: {tr.caps}")
    if tr.cache is not None:
        print(f"feature cache: {tr.cache.describe()}")
    if tr.global_step:
        print(f"resumed at step {tr.global_step} "
              f"(cursor: {tr.stream.cursor.state()})")
    res = tr.fit(verbose=True)
    print(f"\nbest val_acc={res.val_acc:.4f} test_acc={res.test_acc:.4f} "
          f"epochs={res.epochs_to_converge} "
          f"per_epoch={res.per_epoch_time_s:.2f}s "
          f"total={res.total_time_s:.1f}s"
          + (f" cache_hit={res.cache_hit_rate:.3f} "
             f"refills={res.cache_refills}" if res.cache else ""))


if __name__ == "__main__":
    main()
