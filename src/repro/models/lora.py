"""LoRA adapters (Hu et al., arXiv:2106.09685) on a frozen base.

A target projection ``y = x @ W`` gains a trainable low-rank path,
``y = x @ W + (alpha / r) * (x @ A) @ B`` with ``A`` (d_in, r) and ``B``
(r, d_out), both float32 while ``W`` stays in the model's dtype and takes
no gradient.  Adapters live in a flat dict beside the base weights, keyed
``<weight path>/lora_a`` and ``<weight path>/lora_b``; a layer finds its
adapter by the weight's path, so a model run on ``{**base, **adapters}``
is the adapted model and one run on ``base`` alone is the base model.

``TARGETS`` are the MLA projections federated LoRA fine-tunes here (all
four of DeepSeek-V2-Lite's: no query low-rank).  The full-sequence path
applies the low-rank product unmerged, so the base weights are read as
they are stored; the decode path reads merged weights (``weight``).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

TARGETS = ("wq", "wkv_down", "wkv_up", "wo")

Params = Dict[str, jnp.ndarray]


def scale(cfg) -> float:
    """alpha / r, the factor on the low-rank path."""
    return cfg.lora_alpha / cfg.lora_rank


def project(p: Params, key: str, x: jnp.ndarray, cfg) -> jnp.ndarray:
    """``x @ p[key]`` over the last axis, plus the adapter's low-rank path
    where ``p`` holds one for ``key`` (in float32, cast to ``x``'s dtype)."""
    y = jnp.einsum("...d,dh->...h", x, p[key])
    a = p.get(f"{key}/lora_a")
    if a is None:
        return y
    xa = jnp.einsum("...d,dr->...r", x.astype(jnp.float32), a)
    low = jnp.einsum("...r,rh->...h", xa, p[f"{key}/lora_b"])
    return y + (scale(cfg) * low).astype(y.dtype)


def weight(p: Params, key: str, cfg) -> jnp.ndarray:
    """``p[key]`` with its adapter merged in, in the base weight's dtype."""
    w = p[key]
    a = p.get(f"{key}/lora_a")
    if a is None:
        return w
    return (w.astype(jnp.float32)
            + scale(cfg) * (a @ p[f"{key}/lora_b"])).astype(w.dtype)


def init_adapters(base: Params, cfg, key: jax.Array, b_std: float = 0.01) -> Params:
    """Seeded float32 adapters for every target weight in ``base``
    (stacked weights get stacked adapters).  ``A`` is drawn with std
    1/sqrt(d_in) and ``B`` with std ``b_std``: both non-zero, so the first
    rounds already train through the whole low-rank path."""
    out: Params = {}
    r = cfg.lora_rank
    paths = sorted(k for k in base if k.rsplit("/", 1)[-1] in TARGETS)
    for i, path in enumerate(paths):
        w = base[path]
        lead, (d_in, d_out) = w.shape[:-2], w.shape[-2:]
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        out[f"{path}/lora_a"] = (jax.random.normal(ka, lead + (d_in, r))
                                 / math.sqrt(d_in))
        out[f"{path}/lora_b"] = jax.random.normal(kb, lead + (r, d_out)) * b_std
    return out


def fl_loss_fns(model, proxy_tokens: jnp.ndarray, like: Params,
                proxy_chunk: int = 0):
    """``(loss_fn, proxy_loss_fn)`` that federate LoRA adapters through
    ``repro.fl.sparse.SparseAsyncFLTrainer``, the base weights passed as
    ``run``'s ``frozen`` operand.

    ``loss_fn(adapters, x, y, frozen)``: next-token cross-entropy of the
    token rows ``x`` (B, T) (``y`` is unused) and the model's MoE counters
    (``moe_routed``, ``moe_absent``, ``moe_dropped``).
    ``proxy_loss_fn(flat, frozen)``: the same loss on the server's
    ``proxy_tokens`` for flattened adapters shaped like ``like``, in chunks
    of ``proxy_chunk`` sequences (a ``lax.map``: the contribution estimate
    vmaps it over the M leave-one-out models, so a chunk puts M times its
    tokens in flight)."""
    from repro.utils.tree import tree_unflatten_concat

    def loss_fn(adapters, x, y, frozen):
        loss, metrics = model.loss(frozen, {"tokens": x}, adapters=adapters)
        return loss, {k: v for k, v in metrics.items()
                      if k in ("moe_routed", "moe_absent", "moe_dropped")}

    def proxy_loss_fn(flat, frozen):
        adapters = tree_unflatten_concat(flat, like)
        n = proxy_tokens.shape[0]
        c = proxy_chunk or n
        chunks = proxy_tokens.reshape(n // c, c, -1)
        losses = jax.lax.map(
            lambda t: model.loss(frozen, {"tokens": t}, adapters=adapters)[0],
            chunks)
        return jnp.mean(losses)

    return loss_fn, proxy_loss_fn
