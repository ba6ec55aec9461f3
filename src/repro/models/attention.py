"""Attention blocks: GQA (bias / qk-norm / windowed) and MLA.

Two execution paths share one set of weights:

* ``prefill``  — full-sequence training/prefill.  The core is a
  query-chunked online-softmax attention in pure ``lax`` (rematerialized
  in backward) so logits never materialize at O(S^2) and GSPMD can
  partition it; on TPU the Pallas ``flash_attention`` kernel is an
  interchangeable drop-in (see ``repro.kernels``).
* ``decode``   — one token against a (possibly ring / latent) KV cache.
  With the cache sequence dim sharded over the ``model`` mesh axis, the
  softmax reductions lower to all-reduces — distributed flash-decode for
  free from GSPMD.

MLA decode uses the *absorbed* formulation by default (queries projected
into latent space; scores are taken directly against the compressed cache)
— the O(S * kv_lora) deployable path; the naive decompress-then-attend
path is kept for the §Perf baseline.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import lora
from repro.models.layers import ParamBuilder, apply_rope, rms_norm, yarn_get_mscale
from repro.models.kvcache import ring_slot, valid_mask

_NEG_INF = -1e30
ATTN_CHUNK = 512      # query-chunk size for the prefill path


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

def add_gqa_params(pb: ParamBuilder, prefix: str, cfg: ModelConfig, stacked: int = 0):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    lead = (stacked,) if stacked else ()
    ls = ("layers",) if stacked else ()
    pb.add(f"{prefix}/wq", lead + (d, hq * hd), ls + ("embed", "heads"))
    pb.add(f"{prefix}/wk", lead + (d, hkv * hd), ls + ("embed", "heads"))
    pb.add(f"{prefix}/wv", lead + (d, hkv * hd), ls + ("embed", "heads"))
    pb.add(f"{prefix}/wo", lead + (hq * hd, d), ls + ("heads", "embed"))
    if cfg.qkv_bias:
        pb.add(f"{prefix}/bq", lead + (hq * hd,), ls + ("heads",), init="zeros")
        pb.add(f"{prefix}/bk", lead + (hkv * hd,), ls + ("heads",), init="zeros")
        pb.add(f"{prefix}/bv", lead + (hkv * hd,), ls + ("heads",), init="zeros")
    if cfg.qk_norm:
        pb.add(f"{prefix}/q_norm", lead + (hd,), ls + (None,), init="ones")
        pb.add(f"{prefix}/k_norm", lead + (hd,), ls + (None,), init="ones")


def add_mla_params(pb: ParamBuilder, prefix: str, cfg: ModelConfig, stacked: int = 0):
    d, h = cfg.d_model, cfg.n_heads
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    lead = (stacked,) if stacked else ()
    ls = ("layers",) if stacked else ()
    if r_q:
        pb.add(f"{prefix}/wq_down", lead + (d, r_q), ls + ("embed", None))
        pb.add(f"{prefix}/q_norm", lead + (r_q,), ls + (None,), init="ones")
        pb.add(f"{prefix}/wq_up", lead + (r_q, h * (dn + dr)), ls + (None, "heads"))
    else:
        pb.add(f"{prefix}/wq", lead + (d, h * (dn + dr)), ls + ("embed", "heads"))
    pb.add(f"{prefix}/wkv_down", lead + (d, r_kv + dr), ls + ("embed", None))
    pb.add(f"{prefix}/kv_norm", lead + (r_kv,), ls + (None,), init="ones")
    pb.add(f"{prefix}/wkv_up", lead + (r_kv, h * (dn + dv)), ls + (None, "heads"))
    pb.add(f"{prefix}/wo", lead + (h * dv, d), ls + ("heads", "embed"))


# ---------------------------------------------------------------------------
# core attention (query-chunked, online softmax, rematerialized)
# ---------------------------------------------------------------------------

def _chunk_attn(q, k, v, q_offset, causal, window, scale, kv_len):
    """One query chunk: q (B,H,Cq,D); k,v (B,Hkv,S,D) -> (B,H,Cq,Dv)."""
    hq, hkv = q.shape[1], k.shape[1]
    g = hq // hkv
    b, _, cq, _ = q.shape
    s = k.shape[2]
    qg = q.reshape(b, hkv, g, cq, -1)
    logits = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    q_idx = q_offset + jnp.arange(cq)[:, None]
    k_idx = jnp.arange(s)[None, :]
    mask = k_idx < kv_len
    if causal:
        mask &= k_idx <= q_idx
    if window > 0:
        mask &= k_idx > q_idx - window
    logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, cq, -1).astype(q.dtype)


def _use_flash_kernel(q, k) -> bool:
    """Route prefill attention through the Pallas kernel on TPU.

    Conditions: TPU backend, Q and KV head dims equal (the kernel is GQA-
    native but shares one D), and the sequence is long enough that tiling
    pays.  Override with REPRO_ATTN_IMPL=xla|flash."""
    import os
    impl = os.environ.get("REPRO_ATTN_IMPL", "auto")
    if impl == "xla":
        return False
    if impl == "flash":
        return True
    return jax.default_backend() == "tpu" and q.shape[-1] == k.shape[-1] \
        and q.shape[2] >= 256


def attn_core(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    causal: bool = True, window: int = 0, scale: Optional[float] = None,
    chunk: int = ATTN_CHUNK,
) -> jnp.ndarray:
    """Chunked GQA attention.  q (B,Hq,S,D), k/v (B,Hkv,S,Dv) -> (B,Hq,S,Dv).

    On TPU the Pallas flash kernel is the execution path (score/probs stay
    in VMEM); elsewhere — and under GSPMD lowering for the dry-run — the
    query-chunked online-softmax XLA path runs with identical semantics."""
    b, hq, s, d = q.shape
    scale = float(scale) if scale is not None else float(1.0 / (d ** 0.5))
    if v.shape[-1] == d and _use_flash_kernel(q, k):
        from repro.kernels import ops as _kernel_ops

        # forward = Pallas kernel; backward = recompute through the XLA
        # chunked path (the kernel is forward-only — its VJP would need a
        # dedicated backward kernel, so grads rematerialize via XLA)
        def _xla(qq, kk, vv):
            return _attn_core_xla(qq, kk, vv, causal, window, scale, chunk)

        # REPRO_ATTN_IMPL=flash off-TPU explicitly asks for the kernel,
        # which only the interpreter can run there
        backend = ("pallas" if jax.default_backend() == "tpu"
                   else "pallas_interpret")

        @jax.custom_vjp
        def _flash(qq, kk, vv):
            return _kernel_ops.flash_attention(
                qq, kk, vv, causal=causal, window=window, scale=scale,
                backend=backend)

        def _fwd(qq, kk, vv):
            return _flash(qq, kk, vv), (qq, kk, vv)

        def _bwd(res, g):
            _, vjp = jax.vjp(_xla, *res)
            return vjp(g)

        _flash.defvjp(_fwd, _bwd)
        return _flash(q, k, v)
    return _attn_core_xla(q, k, v, causal, window, scale, chunk)


def _attn_core_xla(q, k, v, causal, window, scale, chunk):
    b, hq, s, d = q.shape
    if s <= chunk:
        return _chunk_attn(q, k, v, 0, causal, window, scale, s)
    pad = (-s) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_chunks = q.shape[2] // chunk
    qs = q.reshape(b, hq, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)

    body = jax.checkpoint(
        functools.partial(_chunk_attn, causal=causal, window=window, scale=scale, kv_len=s)
    )

    def step(i, qc):
        return body(qc, k, v, i * chunk)

    out = jax.lax.map(lambda args: step(*args), (jnp.arange(n_chunks), qs))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, hq, n_chunks * chunk, -1)
    return out[:, :, :s]


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def _project_qkv(p, prefix, x, cfg: ModelConfig, positions):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p[f"{prefix}/wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p[f"{prefix}/wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p[f"{prefix}/wv"])
    if cfg.qkv_bias:
        q = q + p[f"{prefix}/bq"]
        k = k + p[f"{prefix}/bk"]
        v = v + p[f"{prefix}/bv"]
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p[f"{prefix}/q_norm"], cfg.norm_eps)
        k = rms_norm(k, p[f"{prefix}/k_norm"], cfg.norm_eps)
    if cfg.is_decoder:  # encoders (hubert) use absolute conv positions, no rope
        q = apply_rope(q.transpose(0, 2, 1, 3), positions, cfg.rope_theta,
                       cfg.rope_scaling).transpose(0, 2, 1, 3)
        k = apply_rope(k.transpose(0, 2, 1, 3), positions, cfg.rope_theta,
                       cfg.rope_scaling).transpose(0, 2, 1, 3)
    return q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def gqa_prefill(
    p: Dict[str, jnp.ndarray], prefix: str, x: jnp.ndarray, cfg: ModelConfig,
    window: int = 0,
) -> jnp.ndarray:
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = _project_qkv(p, prefix, x, cfg, positions)
    out = attn_core(q, k, v, causal=cfg.is_decoder, window=window)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return jnp.einsum("bsh,hd->bsd", out, p[f"{prefix}/wo"])


def gqa_decode(
    p: Dict[str, jnp.ndarray], prefix: str, x: jnp.ndarray, cfg: ModelConfig,
    cache_k: jnp.ndarray, cache_v: jnp.ndarray, pos: jnp.ndarray,
    window: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode.  x (B,1,D); cache k/v (B,Hkv,P,hd).  Returns (y, k', v')."""
    b = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    phys = cache_k.shape[2]
    q, k_new, v_new = _project_qkv(p, prefix, x, cfg, jnp.full((1,), pos))
    slot = ring_slot(pos, phys) if window > 0 else pos
    cache_k = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype), (0, 0, slot, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype), (0, 0, slot, 0))

    g = hq // hkv
    qg = q.reshape(b, hkv, g, hd)
    logits = jnp.einsum(
        "bhgd,bhkd->bhgk", qg.astype(jnp.float32), cache_k.astype(jnp.float32)
    ) / (hd ** 0.5)
    mask = valid_mask(pos, phys, window)
    logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", probs, cache_v.astype(jnp.float32))
    out = out.reshape(b, 1, hq * hd).astype(x.dtype)
    y = jnp.einsum("bsh,hd->bsd", out, p[f"{prefix}/wo"])
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA block (deepseek-v2 / minicpm3)
# ---------------------------------------------------------------------------

def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-0.5, times YaRN's mscale(factor, mscale_all_dim)^2
    where the config scales its rope (DeepSeek-V2's ``softmax_scale``)."""
    scale = 1.0 / ((cfg.qk_nope_dim + cfg.qk_rope_dim) ** 0.5)
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_get_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _mla_q(p, prefix, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        ql = jnp.einsum("bsd,dr->bsr", x, p[f"{prefix}/wq_down"])
        ql = rms_norm(ql, p[f"{prefix}/q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rh->bsh", ql, p[f"{prefix}/wq_up"])
    else:
        q = lora.project(p, f"{prefix}/wq", x, cfg)
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope.transpose(0, 2, 1, 3), positions, cfg.rope_theta,
                        cfg.rope_scaling)
    return q_nope.transpose(0, 2, 1, 3), q_rope  # (B,H,S,dn), (B,H,S,dr)


def _mla_latent(p, prefix, x, cfg: ModelConfig, positions):
    r_kv, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    kv = lora.project(p, f"{prefix}/wkv_down", x, cfg)
    latent = rms_norm(kv[..., :r_kv], p[f"{prefix}/kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., r_kv:], positions, cfg.rope_theta,
                        cfg.rope_scaling)                   # (B,S,dr) shared
    return latent, k_rope


def mla_prefill(
    p: Dict[str, jnp.ndarray], prefix: str, x: jnp.ndarray, cfg: ModelConfig,
    window: int = 0,
) -> jnp.ndarray:
    b, s, _ = x.shape
    h, dn, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
    positions = jnp.arange(s)
    q_nope, q_rope = _mla_q(p, prefix, x, cfg, positions)
    latent, k_rope = _mla_latent(p, prefix, x, cfg, positions)
    kv = lora.project(p, f"{prefix}/wkv_up", latent, cfg).reshape(b, s, h, dn + dv)
    k_nope = kv[..., :dn].transpose(0, 2, 1, 3)
    v = kv[..., dn:].transpose(0, 2, 1, 3)
    # fold the shared rotary key into per-head keys; concatenate nope|rope dims
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, None], (b, h, s, q_rope.shape[-1]))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attn_core(q, k, v, causal=True, window=window,
                    scale=mla_softmax_scale(cfg))
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return lora.project(p, f"{prefix}/wo", out, cfg)


def mla_decode(
    p: Dict[str, jnp.ndarray], prefix: str, x: jnp.ndarray, cfg: ModelConfig,
    cache_latent: jnp.ndarray, cache_krope: jnp.ndarray, pos: jnp.ndarray,
    window: int = 0, absorb: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token MLA decode against the latent cache.

    absorb=True: queries are pulled into latent space through wkv_up (the
    deployable O(S * r_kv) path).  absorb=False decompresses the whole
    cache per step (the naive §Perf baseline).
    """
    b = x.shape[0]
    h, dn, dr, dv, r_kv = (
        cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank,
    )
    phys = cache_latent.shape[1]
    positions = jnp.full((1,), pos)
    q_nope, q_rope = _mla_q(p, prefix, x, cfg, positions)   # (B,H,1,dn),(B,H,1,dr)
    latent_new, krope_new = _mla_latent(p, prefix, x, cfg, positions)
    slot = ring_slot(pos, phys) if window > 0 else pos
    cache_latent = jax.lax.dynamic_update_slice(
        cache_latent, latent_new.astype(cache_latent.dtype), (0, slot, 0))
    cache_krope = jax.lax.dynamic_update_slice(
        cache_krope, krope_new.astype(cache_krope.dtype), (0, slot, 0))

    w_up = lora.weight(p, f"{prefix}/wkv_up", cfg).reshape(r_kv, h, dn + dv)
    w_uk, w_uv = w_up[..., :dn], w_up[..., dn:]
    scale = mla_softmax_scale(cfg)
    lat = cache_latent.astype(jnp.float32)                  # (B,P,r)
    if absorb:
        # q_eff[b,h,r] = sum_dn q_nope[b,h,dn] * w_uk[r,h,dn]
        q_eff = jnp.einsum("bhqd,rhd->bhr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))
        logits = jnp.einsum("bhr,bpr->bhp", q_eff, lat)
    else:
        k_nope = jnp.einsum("bpr,rhd->bhpd", lat, w_uk.astype(jnp.float32))
        logits = jnp.einsum("bhqd,bhpd->bhp", q_nope.astype(jnp.float32), k_nope)
    logits = logits + jnp.einsum(
        "bhqd,bpd->bhp", q_rope.astype(jnp.float32), cache_krope.astype(jnp.float32))
    logits = logits * scale
    mask = valid_mask(pos, phys, window)
    logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if absorb:
        ctx = jnp.einsum("bhp,bpr->bhr", probs, lat)        # context in latent space
        out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv.astype(jnp.float32))
    else:
        v = jnp.einsum("bpr,rhd->bhpd", lat, w_uv.astype(jnp.float32))
        out = jnp.einsum("bhp,bhpd->bhd", probs, v)
    out = out.reshape(b, 1, h * dv).astype(x.dtype)
    y = jnp.einsum("bsh,hd->bsd", out, lora.weight(p, f"{prefix}/wo", cfg))
    return y, cache_latent, cache_krope
