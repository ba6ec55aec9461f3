"""Pallas TPU kernel: fused masked trimmed-mean / coordinate-median.

Robust (Byzantine-tolerant) server aggregation is an order-statistics
reduction over the M client rows: per parameter coordinate, drop the k
smallest and k largest participating values and average the rest
(k = floor((n-1)/2) makes it the coordinate-wise median).  Like
``weighted_aggregate`` the reduction is bandwidth-bound — M * P bytes in,
P bytes out — so the kernel tiles the parameter axis into lane-aligned
VMEM blocks with all M client rows resident on sublanes.

Sorting along sublanes is awkward on the VPU, so selection is rank-based
(matching the ``repro.kernels.ref.robust_trimmed`` oracle exactly): the
rank of row i is the count of participating rows strictly below it (ties
broken by row index), accumulated with an unrolled loop of 2-D
compare/add ops over the M rows — O(M^2 * block) vector work, no sort
primitive.  Ranks are small exact integers, so the kernel agrees with
the oracle bitwise.

Inputs
  updates: (M, P) — client update matrix (bf16 or f32)
  mask:    (M,)   — f32 {0, 1} participation mask
  n_succ:  scalar — f32 participant count (== sum(mask))
  k_trim:  scalar — f32 integer-valued trim depth
Output
  (P,) f32 robust aggregate (zeros when nothing participates).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PARAM_BLOCK = 2048


def _trim_kernel(updates_ref, mask_ref, nk_ref, out_ref):
    x = updates_ref[...].astype(jnp.float32)            # (M, Pb)
    part = mask_ref[...] > 0.5                          # (M, 1)
    n = nk_ref[0, 0]
    k = jnp.maximum(nk_ref[0, 1], 0.0)
    m = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    rank = jnp.zeros_like(x)
    for j in range(m):                                  # unrolled: M is small
        vj = x[j:j + 1, :]                              # (1, Pb)
        beats = (vj < x) | ((vj == x) & (j < row))
        # row j's participation is read as an f32 scalar and compared
        # there: Mosaic extracts only 32-bit scalars from a vector, never
        # an element of the bool mask
        part_j = mask_ref[j, 0] > 0.5
        rank = rank + jnp.where(part_j, beats.astype(jnp.float32), 0.0)
    keep = part & (rank >= k) & (rank < n - k)
    denom = jnp.maximum(n - 2.0 * k, 1.0)
    out_ref[...] = jnp.sum(
        jnp.where(keep, x, 0.0), axis=0, keepdims=True) / denom


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def robust_trimmed(
    updates: jnp.ndarray,
    mask: jnp.ndarray,
    n_succ: jnp.ndarray,
    k_trim: jnp.ndarray,
    interpret: bool = False,
    block: int = PARAM_BLOCK,
) -> jnp.ndarray:
    """Masked per-coordinate trimmed mean (see module docstring)."""
    m, p = updates.shape
    p_pad = (-p) % block
    upd_p = jnp.pad(updates, ((0, 0), (0, p_pad)))
    mask_col = mask.astype(jnp.float32)[:, None]
    nk = jnp.stack([jnp.asarray(n_succ, jnp.float32),
                    jnp.asarray(k_trim, jnp.float32)])[None, :]

    out = pl.pallas_call(
        _trim_kernel,
        grid=((p + p_pad) // block,),
        in_specs=[
            pl.BlockSpec((m, block), lambda i: (0, i)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p + p_pad), jnp.float32),
        interpret=interpret,
    )(upd_p, mask_col, nk)
    return out[0, :p]
