"""Production training launcher: the FL round for any assigned arch.

On real hardware this runs the same step the dry-run compiles for the
16x16 / 2x16x16 meshes; on this CPU container use ``--smoke`` to run the
reduced config of the same family end-to-end.

Usage:
  python -m repro.launch.train --arch qwen1.5-0.5b --smoke --steps 20
  python -m repro.launch.train --arch deepseek-v2-236b --smoke --steps 5
  python -m repro.launch.train --arch qwen3-32b --steps 100 \
      [--seq-shard --microbatch 8 --layout tp]      # TPU cluster
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config, get_smoke_config, list_archs
from repro.core.bandits import GLRCUCB
from repro.core.channels import random_piecewise_env
from repro.data.synthetic import synthetic_lm_batches
from repro.launch.steps import make_fl_train_step, make_train_state_init
from repro.models.model import Model
from repro.optim import adamw
from repro.utils.compile_cache import enable_compile_cache


def make_batch(cfg, batch, seq, key, data_iter=None):
    if cfg.arch_type == "audio":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "frames": jax.random.normal(k1, (batch, seq, cfg.d_model), jnp.bfloat16),
            "labels": jax.random.randint(k2, (batch, seq), 0, cfg.vocab_size),
            "mask": jax.random.bernoulli(k3, cfg.mask_prob, (batch, seq)),
        }
    out = {"tokens": jnp.asarray(next(data_iter))}
    if cfg.arch_type == "vlm":
        out["vision_embeds"] = jax.random.normal(
            key, (batch, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--channels", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ce-chunk", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg=cfg, remat="none" if args.smoke else "full",
                  ce_chunk=args.ce_chunk, seq_shard=args.seq_shard)
    print(f"[train] {cfg.name} ({cfg.arch_type}) — {args.clients} clients, "
          f"{args.channels} channels, {args.steps} rounds")

    sched = GLRCUCB(args.channels, args.clients, history=128)
    env = random_piecewise_env(jax.random.PRNGKey(1), args.channels,
                               args.steps, max(args.steps // 40, 1))
    opt = adamw(args.lr)
    state = make_train_state_init(model, opt, sched, args.clients)(
        jax.random.PRNGKey(0))
    step = jax.jit(make_fl_train_step(
        model, opt, sched, env, args.clients, microbatches=args.microbatch))

    data = (synthetic_lm_batches(args.batch, args.seq, cfg.vocab_size)
            if cfg.arch_type != "audio" else None)
    t0 = time.time()
    for t in range(args.steps):
        batch = make_batch(cfg, args.batch, args.seq,
                           jax.random.fold_in(jax.random.PRNGKey(2), t), data)
        state, mets = step(state, batch, jax.random.fold_in(jax.random.PRNGKey(3), t))
        if t % max(args.steps // 10, 1) == 0 or t == args.steps - 1:
            print(f"  round {t:4d} loss={float(mets['loss']):8.4f} "
                  f"|S_t|={int(mets['n_success'])}/{args.clients} "
                  f"mean_aoi={float(mets['mean_aoi']):.2f}")
    if args.ckpt:
        print("  checkpoint:", save_checkpoint(args.ckpt, args.steps,
                                               {"params": state.params}))
    print(f"[train] done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
