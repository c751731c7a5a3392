"""Serving launcher: batched decode with a (reduced) model.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --batch 4 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_model
from repro.runtime.serve_loop import ServeConfig, serve_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params, _ = init_model(jax.random.PRNGKey(args.seed), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                 (args.batch, args.prompt_len), 2,
                                 min(1000, cfg.vocab_size), jnp.int32)
    t0 = time.time()
    out = serve_batch(params, cfg, prompts,
                      ServeConfig(max_new_tokens=args.new_tokens,
                                  temperature=args.temperature,
                                  seed=args.seed))
    dt = time.time() - t0
    total_new = args.batch * args.new_tokens
    print(f"[serve] {cfg.name}: {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s)")
    for row in out[:2]:
        print("  ", row.tolist())


if __name__ == "__main__":
    main()
