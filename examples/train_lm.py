"""Train a reduced LM arch for a few hundred steps with the fault-tolerant
loop (checkpoint/resume, straggler monitor, optional int8 grad compression).

    PYTHONPATH=src python examples/train_lm.py --arch qwen2-72b --steps 200
"""
import argparse

from repro.configs.base import TrainConfig
from repro.configs.registry import LM_ARCHS, get_config
from repro.data.pipeline import BlockShuffler, LMStream, SyntheticTokens
from repro.runtime import use_compile_cache
from repro.train.lm_loop import LMTrainer


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=list(LM_ARCHS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--shuffle-mode", default="block",
                    choices=["rand", "block", "none"],
                    help="block = COMM-RAND-style constrained shuffle")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    tcfg = TrainConfig(learning_rate=args.lr, remat=False,
                       grad_compression=args.compress_grads)
    corpus = SyntheticTokens(cfg.vocab_size, num_docs=2048,
                             doc_len=args.seq * 2)
    stream = LMStream(corpus, args.batch, args.seq,
                      BlockShuffler(corpus.num_docs, 64,
                                    mode=args.shuffle_mode))
    tr = LMTrainer(cfg, tcfg, stream, ckpt_dir=args.ckpt_dir)
    if tr.step:
        print(f"resumed from step {tr.step}")
    r = tr.run(args.steps)
    print(f"arch={args.arch} steps={args.steps}: "
          f"loss {r['loss_first']:.3f} -> {r['loss_last']:.3f} "
          f"(stragglers: {r['straggler_fraction'] * 100:.1f}%)")


if __name__ == "__main__":
    main()
