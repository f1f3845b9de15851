"""Batched KV-cache decode driver (CPU-scale serving of a reduced model).

Thin CLI over :class:`repro.serving.ServeLoop` — prefills a batch of
prompts then greedily decodes through the loop's single jitted step.
``launch/continuous.py`` drives the same loop interleaved with training.

Usage: PYTHONPATH=src python -m repro.launch.serve --arch jamba-v0.1-52b \
           --batch 4 --prompt-len 16 --new-tokens 24
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.launch import compile_cache
from repro.models import transformer as tr
from repro.serving import ServeLoop


def prefill_into_cache(loop: ServeLoop, tokens):
    """Sequential prefill through the loop's jitted step (one compiled
    executable reused per position — not the eager per-token dispatch
    this driver used to pay)."""
    return loop.prefill(tokens)


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.max_seq < args.prompt_len + args.new_tokens:
        ap.error(f"--max-seq {args.max_seq} < --prompt-len {args.prompt_len}"
                 f" + --new-tokens {args.new_tokens}: decode would index "
                 "past the KV cache")

    cfg = get_smoke_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = tr.init_params(key, cfg, jnp.float32)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)

    loop = ServeLoop(cfg, params, batch=args.batch, max_seq=args.max_seq)
    gen, stats = loop.generate(prompts, args.new_tokens)
    print(f"{cfg.name}: prefill {args.prompt_len} tok in "
          f"{stats['prefill_s']:.2f}s, decoded {args.new_tokens} tok in "
          f"{stats['decode_s']:.2f}s ({stats['tokens_per_s']:.1f} tok/s "
          f"batch={args.batch}, {stats['compile_count']} compile)")
    print("generated[0]:", gen[0].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
