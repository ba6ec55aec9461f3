"""Pallas TPU kernel: GLR change-point statistic, all channels at once.

The GLR-CUCB detector (Alg. 2 lines 15-22) evaluates, per channel, the
sup over split points s of

    s * kl(mu_1:s, mu_1:n) + (n - s) * kl(mu_s+1:n, mu_1:n)

over a length-H reward stream.  Run naively (a python loop over s, as in
reference implementations) this is O(H^2); with a prefix-sum all split
points are evaluated in one vectorized pass.

TPU mapping: channels ride the sublane dimension (blocks of 8), the
stream rides the lane dimension (H padded to a multiple of 128).  Each
grid step loads one (8, H) tile into VMEM, computes the running prefix
sum with a log-step shifted-add scan (``_prefix_sum``: ceil(log2 H) lane
rotations, since Mosaic has no cumsum lowering), evaluates the KL terms for
every split point on the VPU and writes one (8, 1) result tile.
The working set per step is 8*H*4 bytes — H up to ~128k fits VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-6  # float32-safe: 1.0 - 1e-9 rounds to 1.0 and poisons KL with 0*log(0)
CHANNEL_BLOCK = 8


def _kl(p, q):
    p = jnp.clip(p, _EPS, 1.0 - _EPS)
    q = jnp.clip(q, _EPS, 1.0 - _EPS)
    return p * jnp.log(p / q) + (1.0 - p) * jnp.log((1.0 - p) / (1.0 - q))


def _prefix_sum(x, idx):
    """Inclusive prefix sum along lanes: Hillis-Steele log-step scan.

    Each step adds the tile rotated right by d lanes, with the d wrapped-in
    lanes masked to zero.  Every partial sum is a sum of the same samples
    as a sequential cumsum, so {0, 1} streams (small exact integers) give
    the oracle's prefixes bitwise; general floats agree to rounding."""
    d = 1
    while d < x.shape[-1]:
        x = x + jnp.where(idx >= d, pltpu.roll(x, d, 1), 0.0)
        d *= 2
    return x


def _glr_kernel(hist_ref, counts_ref, out_ref):
    hist = hist_ref[...].astype(jnp.float32)          # (Cb, H)
    n = counts_ref[...].astype(jnp.int32)             # (Cb, 1)
    h = hist.shape[-1]

    idx = jax.lax.broadcasted_iota(jnp.int32, (1, h), 1)
    masked = jnp.where(idx < n, hist, 0.0)
    prefix = _prefix_sum(masked, idx)
    total = jnp.sum(masked, axis=-1, keepdims=True)

    s = (idx + 1).astype(jnp.float32)
    n_f = n.astype(jnp.float32)
    mu_all = total / jnp.maximum(n_f, 1.0)
    mu_a = prefix / s
    mu_b = (total - prefix) / jnp.maximum(n_f - s, 1.0)
    stat = s * _kl(mu_a, mu_all) + (n_f - s) * _kl(mu_b, mu_all)
    valid = (idx + 1) <= (n - 1)
    stat = jnp.where(valid, stat, -jnp.inf)
    out_ref[...] = jnp.max(stat, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def glr_scan(hist: jnp.ndarray, counts: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """GLR statistic per channel.  hist: (N, H); counts: (N,).  Returns (N,)."""
    n_chan, h = hist.shape
    # pad channels to the block size; pad H to a lane multiple
    cb = CHANNEL_BLOCK
    n_pad = (-n_chan) % cb
    h_pad = (-h) % 128
    hist_p = jnp.pad(hist.astype(jnp.float32), ((0, n_pad), (0, h_pad)))
    counts_p = jnp.pad(counts.astype(jnp.int32), (0, n_pad))[:, None]
    hp = h + h_pad

    out = pl.pallas_call(
        _glr_kernel,
        grid=((n_chan + n_pad) // cb,),
        in_specs=[
            pl.BlockSpec((cb, hp), lambda i: (i, 0)),
            pl.BlockSpec((cb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((cb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(((n_chan + n_pad), 1), jnp.float32),
        interpret=interpret,
    )(hist_p, counts_p)
    return out[:n_chan, 0]
