"""Shared primitives: parameter registry, norms, RoPE, MLPs, embeddings.

Parameters live in a *flat* dict keyed by '/'-joined paths; a parallel dict
maps each path to a logical PartitionSpec tuple.  Logical axis names are
resolved to mesh axes by ``repro.launch.shardings`` — the model code never
mentions a physical mesh.

Logical axes:
  "embed"   d_model-like dims          -> FSDP axis ("data")
  "heads"   attention-head / ffn dims  -> tensor axis ("model")
  "vocab"   vocabulary                 -> tensor axis ("model")
  "expert"  MoE expert dim             -> tensor axis ("model")
  "layers"  stacked-layer dim          -> unsharded
  None      replicated
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamBuilder:
    """Accumulates (flat-path -> array) params and (flat-path -> logical spec).

    ``meta=True`` records ShapeDtypeStructs instead of materializing arrays —
    the dry-run path (shape+spec metadata only, no host allocation).
    """

    def __init__(self, key: jax.Array, dtype=jnp.bfloat16, meta: bool = False):
        self._key = key
        self.dtype = dtype
        self.meta = meta
        self.params: Dict[str, jnp.ndarray] = {}
        self.specs: Dict[str, Tuple[Optional[str], ...]] = {}

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def add(
        self,
        path: str,
        shape: Sequence[int],
        spec: Tuple[Optional[str], ...],
        init: str = "normal",
        scale: Optional[float] = None,
        dtype=None,
    ) -> None:
        assert path not in self.params, f"duplicate param {path}"
        assert len(spec) == len(shape), f"{path}: spec {spec} vs shape {shape}"
        dtype = dtype or self.dtype
        if self.meta:
            self.params[path] = jax.ShapeDtypeStruct(tuple(shape), dtype)
            self.specs[path] = tuple(spec)
            return
        if init == "zeros":
            val = jnp.zeros(shape, dtype)
        elif init == "ones":
            val = jnp.ones(shape, dtype)
        elif init == "normal":
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            std = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
            val = (jax.random.normal(self._next_key(), shape, jnp.float32) * std).astype(dtype)
        elif init == "embed":
            std = scale if scale is not None else 0.02
            val = (jax.random.normal(self._next_key(), shape, jnp.float32) * std).astype(dtype)
        elif init == "uniform":
            lim = scale if scale is not None else 1.0
            val = (
                jax.random.uniform(self._next_key(), shape, jnp.float32, -lim, lim)
            ).astype(dtype)
        else:
            raise ValueError(init)
        self.params[path] = val
        self.specs[path] = tuple(spec)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
    return out.astype(x.dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies for the (even) rotary dims — (head_dim // 2,)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_get_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature factor, 0.1 * mscale * ln(scale) + 1."""
    if scale <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, theta: float,
                         max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))) / (
        2 * math.log(theta))


def yarn_freqs(head_dim: int, theta: float, scaling) -> np.ndarray:
    """YaRN inverse frequencies (head_dim // 2,), as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` computes them: the dims that turn
    fewer than ``beta_slow`` times over the original context are
    interpolated (divided by ``factor``), those that turn more than
    ``beta_fast`` times keep their frequency, and a linear ramp between the
    two correction dims blends them."""
    base = theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    extra, inter = 1.0 / base, 1.0 / (scaling.factor * base)
    n = scaling.original_max_position_embeddings
    low = max(math.floor(_yarn_correction_dim(scaling.beta_fast, head_dim,
                                              theta, n)), 0)
    high = min(math.ceil(_yarn_correction_dim(scaling.beta_slow, head_dim,
                                              theta, n)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                     # 1: extrapolate, 0: interpolate
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling=None) -> jnp.ndarray:
    """x: (..., S, D_rot) with positions (..., S) or (S,).  Pairs (2i, 2i+1).

    ``scaling`` (a ``RopeScaling``) turns on YaRN: its inverse frequencies,
    and cos/sin scaled by mscale / mscale_all_dim (1 where the two agree).
    DeepSeek-V2 de-interleaves each head's rotary dims before rotating
    halves; rotating the interleaved pairs in place gives the same q.k
    products (both sides see the same permutation)."""
    d = x.shape[-1]
    if scaling is None:
        inv, m = rope_freqs(d, theta), 1.0
    else:
        inv = jnp.asarray(yarn_freqs(d, theta, scaling))
        m = (yarn_get_mscale(scaling.factor, scaling.mscale)
             / yarn_get_mscale(scaling.factor, scaling.mscale_all_dim))
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU FFN: down( silu(x @ gate) * (x @ up) )."""
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u, w_down)


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    """Plain 2-layer GELU MLP (hubert-style encoder FFN)."""
    h = jnp.einsum("...d,df->...f", x, w_in) + b_in
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, w_out) + b_out


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def add_mlp_params(pb: ParamBuilder, prefix: str, d_model: int, d_ff: int,
                   act: str, stacked: int = 0):
    lead = (stacked,) if stacked else ()
    lspec = ("layers",) if stacked else ()
    if act == "silu":
        pb.add(f"{prefix}/w_gate", lead + (d_model, d_ff), lspec + ("embed", "heads"))
        pb.add(f"{prefix}/w_up", lead + (d_model, d_ff), lspec + ("embed", "heads"))
        pb.add(f"{prefix}/w_down", lead + (d_ff, d_model), lspec + ("heads", "embed"))
    else:
        pb.add(f"{prefix}/w_in", lead + (d_model, d_ff), lspec + ("embed", "heads"))
        pb.add(f"{prefix}/b_in", lead + (d_ff,), lspec + ("heads",), init="zeros")
        pb.add(f"{prefix}/w_out", lead + (d_ff, d_model), lspec + ("heads", "embed"))
        pb.add(f"{prefix}/b_out", lead + (d_model,), lspec + (None,), init="zeros")


def apply_mlp(p: Dict[str, jnp.ndarray], prefix: str, x: jnp.ndarray, act: str):
    if act == "silu":
        return swiglu(x, p[f"{prefix}/w_gate"], p[f"{prefix}/w_up"], p[f"{prefix}/w_down"])
    return gelu_mlp(
        x, p[f"{prefix}/w_in"], p[f"{prefix}/b_in"], p[f"{prefix}/w_out"], p[f"{prefix}/b_out"]
    )
