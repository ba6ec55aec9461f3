"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: each kernel's test sweeps shapes/dtypes
and asserts allclose against the function here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-6  # float32-safe: 1.0 - 1e-9 rounds to 1.0 and poisons KL with 0*log(0)


# ---------------------------------------------------------------------------
# glr_scan
# ---------------------------------------------------------------------------

def bernoulli_kl(p, q):
    p = jnp.clip(p, _EPS, 1.0 - _EPS)
    q = jnp.clip(q, _EPS, 1.0 - _EPS)
    return p * jnp.log(p / q) + (1.0 - p) * jnp.log((1.0 - p) / (1.0 - q))


def glr_scan(hist: jnp.ndarray, counts: jnp.ndarray) -> jnp.ndarray:
    """GLR change-point statistic for each channel.

    hist:   (N, H) reward streams (entries at index >= counts[i] ignored)
    counts: (N,)   valid lengths
    returns (N,) sup_s [ s*kl(mu_1:s, mu_1:n) + (n-s)*kl(mu_s+1:n, mu_1:n) ],
    -inf where n < 2.
    """
    h = hist.shape[-1]
    idx = jnp.arange(h)
    n = counts.astype(jnp.int32)[:, None]                     # (N, 1)
    masked = jnp.where(idx[None, :] < n, hist, 0.0)
    prefix = jnp.cumsum(masked, axis=-1)
    total = jnp.sum(masked, axis=-1, keepdims=True)
    s = (idx + 1).astype(jnp.float32)[None, :]
    n_f = n.astype(jnp.float32)
    mu_all = total / jnp.maximum(n_f, 1.0)
    mu_a = prefix / s
    mu_b = (total - prefix) / jnp.maximum(n_f - s, 1.0)
    stat = s * bernoulli_kl(mu_a, mu_all) + (n_f - s) * bernoulli_kl(mu_b, mu_all)
    valid = (idx[None, :] + 1 >= 1) & (idx[None, :] + 1 <= n - 1)
    return jnp.max(jnp.where(valid, stat, -jnp.inf), axis=-1)


# ---------------------------------------------------------------------------
# glr_step — streaming (carried prefix-sum) detector
# ---------------------------------------------------------------------------
#
# The recompute path above re-derives the window prefix sum from the raw
# history with an O(H) ``cumsum`` on every detector call.  The streaming
# path instead carries, per channel,
#
#   cum[j]   cumulative stream total C_k = z_1 + .. + z_k for the sample k
#            most recently written to ring slot j
#   total    running stream total C_c (c = samples since restart)
#   base     C_{c-n} where n = min(c, H) — the cumulative total just
#            before the window's oldest sample (0 until the ring wraps)
#
# so the window prefix at split s is ``cum[slot(s)] - base`` and the window
# total is ``total - base`` — no cumsum, and the per-step maintenance is one
# O(N) scatter.  For {0, 1} rewards every quantity is an exactly
# representable small integer, so the streaming statistic equals the
# recompute statistic *bitwise* (general float streams agree to ~1e-5; see
# tests/test_glr_stream.py).


def glr_split_offsets(h: int):
    """Powers of two <= h — the geometric split-grid offsets (static)."""
    offs = []
    d = 1
    while d <= h:
        offs.append(d)
        d *= 2
    return jnp.asarray(offs, jnp.int32)


def glr_stream_append(cum, total, base, counts, r_vec, sched):
    """Append one masked sample per channel to the streaming detector state.

    cum: (N, H) prefix ring;  total/base: (N,);  counts: (N,) samples
    since restart (pre-append, float or int);  r_vec: (N,) rewards;
    sched: (N,) bool — which channels observed a sample this round.
    Returns the updated ``(cum, total, base)``.  O(N) scatter/gather —
    independent of H.  Correct across ring wraparound (the evicted sample's
    ``cum`` entry becomes the new ``base``) and restarts (zeroed
    counts/total/base make every stale slot invalid; the ring itself need
    not be cleared — split positions only ever reach the n newest slots).

    The raw samples are never materialized: the statistic reads only the
    carried prefixes (a sample is recoverable as the difference of
    consecutive ``cum`` entries if ever needed).
    """
    n, h = cum.shape
    c_prev = counts.astype(jnp.int32)
    w = jnp.mod(c_prev, h)                     # ring slot of this append
    rows = jnp.arange(n)
    evict = cum[rows, w]                       # C_{c-H} when the ring is full
    full = c_prev >= h
    base2 = jnp.where(sched & full, evict, base)
    total2 = jnp.where(sched, total + r_vec, total)
    cum2 = cum.at[rows, w].set(jnp.where(sched, total2, evict))
    return cum2, total2, base2


def _stream_stat_terms(P, W, s, n):
    """Shared GLR-statistic arithmetic for both split evaluators.

    P: window prefix sums at the candidate splits; W: window totals;
    s: split positions (int); n: window lengths (int).  Division guards are
    the identity on valid splits (1 <= s <= n-1), so values match the
    recompute reference exactly there.
    """
    s_f = jnp.maximum(s.astype(jnp.float32), 1.0)
    n_f = n.astype(jnp.float32)
    mu_all = W / jnp.maximum(n_f, 1.0)
    mu_a = P / s_f
    mu_b = (W - P) / jnp.maximum(n_f - s_f, 1.0)
    return (s_f * bernoulli_kl(mu_a, mu_all)
            + (n_f - s_f) * bernoulli_kl(mu_b, mu_all))


def glr_stream_stat(cum, total, base, counts, split_grid: str = "all"):
    """GLR statistic from the carried prefix state — no cumsum, no history.

    ``split_grid="all"`` evaluates every split (per ring slot j the split
    position is s_j = n - ((w - j) mod H), w the newest slot): O(H)
    elementwise work but nothing sequential.  ``"geometric"`` gathers only
    the O(log H) splits at power-of-two distances from either window end
    (s or n - s a power of two) — the sup over that subgrid lower-bounds the
    dense sup, trading a bounded detection delay for a ~H/log H cheaper
    test.  Returns (N,) statistics; -inf where n < 2.
    """
    n_chan, h = cum.shape
    c = counts.astype(jnp.int32)[:, None]
    n = jnp.minimum(c, h)
    W = (total - base)[:, None]
    if split_grid == "geometric":
        d = glr_split_offsets(h)[None, :]                    # (1, L)
        s = jnp.concatenate(
            [jnp.broadcast_to(d, (n_chan, d.shape[1])), n - d], axis=1)
        slot = jnp.mod(c - n + s - 1, h)                     # slot of sample s
        P = jnp.take_along_axis(cum, slot, axis=1) - base[:, None]
    else:
        j = jnp.arange(h)[None, :]
        w_last = jnp.mod(c - 1, h)
        s = n - jnp.mod(w_last - j, h)                       # split at slot j
        P = cum - base[:, None]
    stat = _stream_stat_terms(P, W, s, n)
    valid = (s >= 1) & (s <= n - 1)
    return jnp.max(jnp.where(valid, stat, -jnp.inf), axis=-1)


def glr_step(cum, total, base, counts, r_vec, sched,
             split_grid: str = "all"):
    """Fused streaming detector step: prefix-ring append + GLR test.

    The semantics of record for the Pallas kernel in
    ``repro.kernels.glr_step``: one masked sample append per channel
    (``glr_stream_append``) followed by the statistic over the post-append
    state (``glr_stream_stat``).  Returns ``(cum, total, base, stats)``.
    """
    cum2, total2, base2 = glr_stream_append(
        cum, total, base, counts, r_vec, sched)
    c2 = counts.astype(jnp.int32) + sched.astype(jnp.int32)
    stats = glr_stream_stat(cum2, total2, base2, c2, split_grid)
    return cum2, total2, base2, stats


# ---------------------------------------------------------------------------
# weighted_aggregate
# ---------------------------------------------------------------------------

def weighted_aggregate(updates: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Eq. 7 server aggregation: out[p] = sum_m scale[m] * updates[m, p].

    updates: (M, P) client update matrix (any float dtype)
    scale:   (M,)   pre-combined  mask * zeta / |S_t|  coefficients (f32)
    returns (P,) f32 aggregate.
    """
    return jnp.sum(scale[:, None] * updates.astype(jnp.float32), axis=0)


# ---------------------------------------------------------------------------
# robust_trimmed — masked per-coordinate trimmed mean / median
# ---------------------------------------------------------------------------

def robust_trimmed(updates: jnp.ndarray, mask: jnp.ndarray,
                   n_succ: jnp.ndarray, k_trim: jnp.ndarray) -> jnp.ndarray:
    """Masked coordinate-wise trimmed mean via rank selection.

    updates: (M, P) client update matrix (any float dtype)
    mask:    (M,)   f32 {0, 1} participation mask
    n_succ:  scalar f32 participant count (== sum(mask))
    k_trim:  scalar f32 integer-valued trim depth
    returns (P,) f32: per coordinate, the mean of the participating values
    with the ``k_trim`` smallest and ``k_trim`` largest dropped.  With
    ``k_trim = floor((n-1)/2)`` this is exactly the coordinate-wise median
    (odd n: middle element; even n: mean of the two middles).  Zeros when
    no row participates.

    Selection is rank-based rather than sort-based so the Pallas kernel can
    reproduce it with 2-D compare/accumulate ops only: a participating row's
    per-coordinate rank is the number of participating rows strictly below
    it, ties broken by row index.  Ranks are small exact integers and the
    kept values are summed in row order, so kernel and oracle agree bitwise.
    """
    x = updates.astype(jnp.float32)
    m = x.shape[0]
    part = mask > 0.5
    i = jnp.arange(m)
    tie_lo = (i[None, :] < i[:, None])[:, :, None]            # j beats i on ties
    beats = (x[None, :, :] < x[:, None, :]) | ((x[None, :, :] == x[:, None, :]) & tie_lo)
    rank = jnp.sum(
        jnp.where(part[None, :, None], beats, False).astype(jnp.float32),
        axis=1)                                               # (M, P)
    k = jnp.maximum(k_trim, 0.0)
    keep = part[:, None] & (rank >= k) & (rank < n_succ - k)
    denom = jnp.maximum(n_succ - 2.0 * k, 1.0)
    return jnp.sum(jnp.where(keep, x, 0.0), axis=0) / denom


# ---------------------------------------------------------------------------
# client_gather
# ---------------------------------------------------------------------------

def client_gather(client_x: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Rows ``ids`` (M,) of the (N, ...) per-client array: (M, ...)."""
    return jnp.take(client_x, ids, axis=0)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

def mha_attention(
    q: jnp.ndarray,          # (B, Hq, S, D)
    k: jnp.ndarray,          # (B, Hkv, S, D)
    v: jnp.ndarray,          # (B, Hkv, S, D)
    causal: bool = True,
    window: int = 0,         # 0 => full; else sliding window of this width
    scale: float | None = None,
) -> jnp.ndarray:
    """Grouped-query attention oracle (naive O(S^2) reference)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    k_exp = jnp.repeat(k, group, axis=1)
    v_exp = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k_exp.astype(jnp.float32)) * scale
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= ki <= qi
    if window > 0:
        mask &= ki > qi - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v_exp.astype(jnp.float32))
    return out.astype(q.dtype)
