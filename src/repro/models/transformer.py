"""Block composition + scan-over-layers stacking.

A *block* is (pre-norm -> mixer -> residual -> pre-norm -> FFN -> residual)
where the mixer is GQA / MLA attention, an SSD (mamba-2) scan, or an
RG-LRU recurrence, and the FFN is a SwiGLU/GELU MLP or a routed MoE
(mamba blocks carry no separate FFN, as in the reference architecture).

Homogeneous stacks are *scanned*: per-layer parameters are stacked along a
leading ``layers`` axis and the whole depth is one ``lax.scan`` — keeping
HLO size O(1) in depth, which is what makes compiling 60-layer 200B-param
configs on 512 devices tractable.  Heterogeneous stacks (recurrentgemma's
1-attention-per-3-layers pattern, deepseek's leading dense layer) unroll
in Python.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.act_sharding import constrain
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import ParamBuilder, add_mlp_params, apply_mlp, rms_norm


def _ffn_is_moe(cfg: ModelConfig, layer_idx: int) -> bool:
    return bool(cfg.n_experts) and layer_idx >= cfg.first_k_dense


def add_block_params(
    pb: ParamBuilder, prefix: str, cfg: ModelConfig, kind: str,
    moe_ffn: bool, stacked: int = 0,
):
    d = cfg.d_model
    lead = (stacked,) if stacked else ()
    ls = ("layers",) if stacked else ()
    pb.add(f"{prefix}/norm1", lead + (d,), ls + (None,), init="ones")
    if kind == "attn":
        if cfg.attention == "mla":
            attn.add_mla_params(pb, f"{prefix}/attn", cfg, stacked)
        else:
            attn.add_gqa_params(pb, f"{prefix}/attn", cfg, stacked)
    elif kind == "ssm":
        ssm_mod.add_ssm_params(pb, f"{prefix}/ssm", cfg, stacked)
        return  # mamba blocks: no separate FFN
    elif kind == "rglru":
        rglru_mod.add_rglru_params(pb, f"{prefix}/rglru", cfg, stacked)
    else:
        raise ValueError(kind)
    pb.add(f"{prefix}/norm2", lead + (d,), ls + (None,), init="ones")
    if moe_ffn:
        moe_mod.add_moe_params(pb, f"{prefix}/moe", cfg, stacked)
    else:
        add_mlp_params(pb, f"{prefix}/mlp", d, cfg.d_ff, cfg.mlp_act, stacked)


def moe_block_ffn(p, prefix: str, x: jnp.ndarray, cfg: ModelConfig):
    """A block's MoE FFN: the dropless held-experts layer where the config
    sets no capacity, else the capacity-bound one.  Returns (out, stats):
    the load-balance loss as ``balance`` and the dropless layer's counters."""
    if cfg.capacity_factor == 0:
        return moe_mod.held_moe_ffn(p, prefix, x, cfg)
    out, aux = moe_mod.moe_ffn(p, prefix, x, cfg)
    return out, {"balance": aux}


def block_forward(
    p: Dict[str, jnp.ndarray], prefix: str, x: jnp.ndarray, cfg: ModelConfig,
    kind: str, moe_ffn: bool, window: int = 0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence block.  Returns (x, stats): ``balance`` (the MoE
    load-balance loss, 0 without experts) and the MoE layer's counters."""
    aux = {"balance": jnp.zeros((), jnp.float32)}
    h = rms_norm(x, p[f"{prefix}/norm1"], cfg.norm_eps)
    if kind == "attn":
        if cfg.attention == "mla":
            h = attn.mla_prefill(p, f"{prefix}/attn", h, cfg, window=window)
        else:
            h = attn.gqa_prefill(p, f"{prefix}/attn", h, cfg, window=window)
    elif kind == "ssm":
        h = ssm_mod.ssm_forward(p, f"{prefix}/ssm", h, cfg)
        return x + h, aux
    elif kind == "rglru":
        h = rglru_mod.rglru_forward(p, f"{prefix}/rglru", h, cfg)
    x = x + h
    h = rms_norm(x, p[f"{prefix}/norm2"], cfg.norm_eps)
    if moe_ffn:
        h, aux = moe_block_ffn(p, f"{prefix}/moe", h, cfg)
    else:
        h = apply_mlp(p, f"{prefix}/mlp", h, cfg.mlp_act)
    return x + h, aux


def block_decode(
    p: Dict[str, jnp.ndarray], prefix: str, x: jnp.ndarray, cfg: ModelConfig,
    kind: str, moe_ffn: bool, cache: Dict[str, jnp.ndarray], pos: jnp.ndarray,
    window: int = 0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token block step.  cache is this block's (unstacked) cache dict."""
    h = rms_norm(x, p[f"{prefix}/norm1"], cfg.norm_eps)
    new_cache: Dict[str, jnp.ndarray] = {}
    if kind == "attn":
        if cfg.attention == "mla":
            h, lat, kr = attn.mla_decode(
                p, f"{prefix}/attn", h, cfg, cache["latent"], cache["k_rope"], pos,
                window=window)
            new_cache = {"latent": lat, "k_rope": kr}
        else:
            h, ck, cv = attn.gqa_decode(
                p, f"{prefix}/attn", h, cfg, cache["k"], cache["v"], pos,
                window=window)
            new_cache = {"k": ck, "v": cv}
    elif kind == "ssm":
        h, new_cache = ssm_mod.ssm_decode(p, f"{prefix}/ssm", h, cfg, cache)
        return x + h, new_cache
    elif kind == "rglru":
        h, new_cache = rglru_mod.rglru_decode(p, f"{prefix}/rglru", h, cfg, cache)
    x = x + h
    h = rms_norm(x, p[f"{prefix}/norm2"], cfg.norm_eps)
    if moe_ffn:
        h, _ = moe_block_ffn(p, f"{prefix}/moe", h, cfg)
    else:
        h = apply_mlp(p, f"{prefix}/mlp", h, cfg.mlp_act)
    return x + h, new_cache


# ---------------------------------------------------------------------------
# stacking
# ---------------------------------------------------------------------------

def _slice_tree(tree: Dict[str, jnp.ndarray], i) -> Dict[str, jnp.ndarray]:
    return {k: v[i] for k, v in tree.items()}


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)  # "full"


def scanned_forward(
    stacked: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: ModelConfig,
    kind: str, moe_ffn: bool, window: int = 0, remat: str = "full",
    seq_shard: bool = False,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Scan a homogeneous block stack.  ``stacked`` values have leading L dim.
    Returns (x, stats summed over the layers).

    ``seq_shard``: shard the residual stream over the tensor axis on the
    sequence dim between blocks (Megatron sequence parallelism) — the
    checkpointed scan carries shrink by the tensor-axis size, which is what
    keeps 60-layer x 1M-token remat within HBM (§Perf)."""
    mid = "seq" if seq_shard else None

    def body(carry, layer_params):
        y, aux = block_forward(layer_params, "b", carry, cfg, kind, moe_ffn, window)
        return constrain(y, "batch", mid, None), aux

    body = _remat(body, remat)
    x, auxs = jax.lax.scan(body, x, stacked)
    return x, jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), auxs)


def scanned_decode(
    stacked: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: ModelConfig,
    kind: str, moe_ffn: bool, cache: Dict[str, jnp.ndarray], pos: jnp.ndarray,
    window: int = 0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Scan decode through a stack; cache values also carry a leading L dim."""

    def body(carry, xs):
        layer_params, layer_cache = xs
        y, new_cache = block_decode(
            layer_params, "b", carry, cfg, kind, moe_ffn, layer_cache, pos, window)
        return y, new_cache

    x, new_cache = jax.lax.scan(body, x, (stacked, cache))
    return x, new_cache
