"""Production serving launcher: batched greedy decode for any assigned arch.

Usage:
  python -m repro.launch.serve --arch mamba2-1.3b --smoke --tokens 32
  python -m repro.launch.serve --arch qwen2.5-32b --smoke --window 64
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config, list_archs
from repro.launch.steps import make_serve_step
from repro.models import build_model
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=[a for a in list_archs() if a != "hubert-xlarge"])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--context", type=int, default=128)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--window", type=int, default=0,
                    help=">0: sliding-window ring cache (long-context mode)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg, remat="none")
    params, _ = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(args.batch, args.context, window=args.window or None)
    serve = jax.jit(make_serve_step(model, window=args.window))

    tok = jnp.zeros((args.batch,), jnp.int32)
    t0 = time.time()
    for _ in range(args.tokens):
        tok, cache = serve(params, cache, tok)
    # the loop only dispatches async work; retire it before reading the
    # clock or tok/s includes un-executed steps
    jax.block_until_ready((tok, cache))
    dt = time.time() - t0
    print(f"[serve] {cfg.name}: {args.tokens} tokens x {args.batch} seqs "
          f"in {dt:.2f}s ({args.batch*args.tokens/dt:.1f} tok/s), "
          f"cache pos={int(cache['pos'])}")


if __name__ == "__main__":
    main()
