"""Batched LM serving on CPU: prefill a batch of prompts into a KV cache,
then decode tokens step by step (reduced config of any assigned arch).

    PYTHONPATH=src python examples/serve_lm.py --arch gemma3-1b --tokens 16
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import LM_ARCHS, get_config
from repro.launch.specs import materialize, prefill_batch_specs
from repro.models.lm import transformer
from repro.runtime import use_compile_cache
from repro.train.train_step import make_decode_step, make_prefill_step


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=list(LM_ARCHS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    print(f"arch={args.arch} (reduced: {cfg.num_layers}L d={cfg.d_model})")
    params = transformer.init(cfg, jax.random.key(0), max_seq=256)

    total = args.prompt_len + args.tokens
    batch = materialize(prefill_batch_specs(cfg, args.batch,
                                            args.prompt_len))
    batch["tokens"] = jax.random.randint(
        jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab_size,
        jnp.int32)

    prefill = make_prefill_step(cfg)
    decode = make_decode_step(cfg)

    t0 = time.perf_counter()
    logits, cache = prefill(params, batch)
    jax.block_until_ready(logits)
    print(f"prefill({args.prompt_len} tok x {args.batch}): "
          f"{(time.perf_counter() - t0) * 1e3:.1f}ms")

    # grow the cache to the full serving length
    full = transformer.init_cache(cfg, args.batch, total, jnp.bfloat16)
    if not cfg.rwkv:
        full["k"] = jax.lax.dynamic_update_slice_in_dim(
            full["k"], cache["k"].astype(full["k"].dtype), 0, axis=2)
        full["v"] = jax.lax.dynamic_update_slice_in_dim(
            full["v"], cache["v"].astype(full["v"].dtype), 0, axis=2)
        for key in ("h", "conv", "ck", "cv"):
            if key in cache:
                full[key] = cache[key].astype(full[key].dtype)
        cache = full

    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for t in range(args.tokens):
        logits, cache = decode(params, cache, tok, args.prompt_len + t)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    toks = jnp.concatenate(out, axis=1)
    print(f"decoded {args.tokens} tokens x {args.batch} seqs in "
          f"{dt * 1e3:.1f}ms ({args.tokens * args.batch / dt:.0f} tok/s)")
    print("sampled ids (greedy):", toks[0].tolist())


if __name__ == "__main__":
    main()
