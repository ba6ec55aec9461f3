"""Plain reference for the ``serve256`` deployment: one FL job's schedule.

What each answer has to be, from the paper (arXiv:2503.01324) alone:

* GLR-CUCB (Alg. 2): each round schedule the M channels of highest UCB,
  ``mu + gamma * sqrt(3 log(t - tau) / (2 D))`` (Eq. 30), with
  ``t - tau`` floored at 2; a channel never seen since the last restart
  ranks above every seen one, unseen channels in an order drawn from the
  request key.  Player j takes the ((j + t) mod M)-th of them (line 10).
  Each scheduled channel's mean is updated as the running mean
  ``(mu D + r) / (D + 1)``; every ``detector_stride`` rounds the GLR
  change-point test runs on each scheduled channel's last
  ``min(D, history)`` rewards, and any channel whose statistic reaches
  ``beta(n, delta) = (1 + 1/n) log(3 n sqrt(n) / delta)`` with at least
  ``min_samples`` samples restarts the bandit (D = 0, mu = 0, tau = t).
* The Sec.-V matcher: client priority
  ``lambda_i = (1 - beta_t) C_i / max C + beta_t a_i / a_max`` with
  ``beta_t = beta V_t / V_max`` (Eq. 36-40, running maxima over the
  job's rounds), the i-th best scheduled channel by UCB to the client of
  i-th highest priority.

It imports nothing of the program.  It keeps each channel's raw rewards
in a ring and takes the window's prefix sums with ``cumsum`` (the program
carries prefix sums instead), and it replays each job's requests in
order: every request is decided from the job's own history, learning from
the assignment the service actually gave (so one wrong answer is counted
once and does not derail the rest of the job).

``decide(hist, cfg, dtype)`` returns the reference's assignment for every
request; ``dtype=jnp.bfloat16`` computes it a precision below the
configuration's float32 (the control).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-6      # Bernoulli KL arguments kept off 0 and 1


def _kl(p, q):
    p = jnp.clip(p, _EPS, 1.0 - _EPS)
    q = jnp.clip(q, _EPS, 1.0 - _EPS)
    return p * jnp.log(p / q) + (1.0 - p) * jnp.log((1.0 - p) / (1.0 - q))


def _glr(ring, count, h, dt):
    """GLR statistic of each channel's last ``min(count, h)`` rewards."""
    n = jnp.minimum(count, h).astype(jnp.int32)                 # (N,)
    oldest = jnp.mod(count.astype(jnp.int32) - n, h)            # ring slot
    s = jnp.arange(1, h + 1)                                    # split s
    idx = jnp.mod(oldest[:, None] + s[None, :] - 1, h)
    x = jnp.take_along_axis(ring, idx, axis=1)
    x = jnp.where(s[None, :] <= n[:, None], x, 0)
    prefix = jnp.cumsum(x, axis=1)
    total = prefix[:, -1:]
    n_f = n[:, None].astype(dt)
    s_f = s[None, :].astype(dt)
    mu = total / jnp.maximum(n_f, 1)
    stat = (s_f * _kl(prefix / s_f, mu)
            + (n_f - s_f) * _kl((total - prefix) / jnp.maximum(n_f - s_f, 1),
                                mu))
    valid = s[None, :] <= n[:, None] - 1
    return jnp.max(jnp.where(valid, stat, -jnp.inf), axis=1), n


def _job(hist, p, dt):
    """Replay one job: scan over its requests."""
    nch, m, h = p["n_channels"], p["n_clients"], p["history"]
    gamma, delta = dt(p["gamma"]), p["delta"]

    def step(carry, x):
        mu, cnt, tau, ring, t, vmax, amax = carry
        rewards, key, contrib, aoi, served, valid = x
        rewards, contrib, aoi = (rewards.astype(dt), contrib.astype(dt),
                                 aoi.astype(dt))
        # ---- decide: UCB schedule (Eq. 30, Alg. 2 line 10) ----
        since = jnp.maximum((t - tau).astype(dt), 2)
        bonus = jnp.sqrt(3 * jnp.log(since) / (2 * jnp.maximum(cnt, 1)))
        seen = cnt > 0
        score = jnp.where(seen, mu + gamma * bonus, dt(1e9))
        k_sel = jax.random.split(key)[1]
        jitter = jnp.where(seen, 0,
                           (jax.random.uniform(k_sel, (nch,)) * 1e6).astype(dt))
        top = jnp.argsort(-(score + jitter), stable=True)[:m]
        channels = top[(jnp.arange(m) + t) % m]
        # ---- match: Eq. 36-40 ----
        v = jnp.sum((aoi - jnp.mean(aoi)) ** 2)
        vmax2 = jnp.maximum(vmax, v)
        amax2 = jnp.maximum(amax, jnp.max(aoi))
        beta_t = dt(p["matcher_beta"]) * jnp.where(vmax2 > 0, v / vmax2, 0)
        c_tilde = contrib / jnp.maximum(jnp.max(contrib), 1e-12)
        a_tilde = jnp.where(amax2 > 0, aoi / amax2, 0)
        lam = (1 - beta_t) * c_tilde + beta_t * a_tilde
        chan_rank = jnp.argsort(-score[channels], stable=True)
        client_rank = jnp.argsort(-lam, stable=True)
        mine = jnp.zeros((m,), jnp.int32).at[client_rank].set(
            channels[chan_rank])
        # ---- learn from the answer the service gave ----
        a = jnp.clip(served, 0, nch - 1)
        sched = jnp.zeros((nch,), bool).at[a].set(True)
        r = jnp.zeros((nch,), dt).at[a].set(rewards[a])
        mu2 = jnp.where(sched, (mu * cnt + r) / (cnt + 1), mu)
        cnt2 = jnp.where(sched, cnt + 1, cnt)
        slot = jnp.mod(cnt.astype(jnp.int32), h)
        rows = jnp.arange(nch)
        ring2 = ring.at[rows, slot].set(jnp.where(sched, r, ring[rows, slot]))
        stat, n = _glr(ring2, cnt2, h, dt)
        n_f = jnp.maximum(n, 1).astype(dt)
        thresh = (1 + 1 / n_f) * jnp.log(3 * n_f * jnp.sqrt(n_f) / delta)
        fire = ((t % p["detector_stride"]) == 0) & jnp.any(
            sched & (stat >= thresh) & (n >= p["min_samples"]))
        new = (jnp.where(fire, 0, mu2), jnp.where(fire, 0, cnt2),
               jnp.where(fire, t, tau), ring2, t + 1, vmax2, amax2)
        carry = jax.tree_util.tree_map(
            lambda x1, x0: jnp.where(valid, x1, x0), new, carry)
        return carry, mine

    init = (jnp.zeros((nch,), dt), jnp.zeros((nch,), dt),
            jnp.zeros((), jnp.int32), jnp.zeros((nch, h), dt),
            jnp.zeros((), jnp.int32), jnp.zeros((), dt), jnp.ones((), dt))
    _, mine = jax.lax.scan(step, init, hist)
    return mine


@functools.lru_cache(maxsize=None)
def _compiled(params, dt):
    p = dict(params)
    return jax.jit(jax.vmap(lambda hist: _job(hist, p, dt)))


def decide(jobs, scheduler, dtype=jnp.float32):
    """The reference's assignment for every request of each job.

    ``jobs``: list of job histories, each a dict of per-request arrays
    ``rewards (R, N)``, ``keys (R, 2) uint32``, ``contrib (R, M)``,
    ``aoi (R, M)`` and ``served (R, M)`` (the service's answers).
    ``scheduler``: the configuration's scheduler and matcher settings.
    Returns a list of (R, M) int arrays.
    """
    lens = [len(j["served"]) for j in jobs]
    length = 1 << max(1, (max(lens) - 1).bit_length())   # few shapes
    nch, m = scheduler["n_channels"], scheduler["n_clients"]

    def pad(key, shape, dt):
        out = np.zeros((len(jobs), length) + shape, dt)
        for i, j in enumerate(jobs):
            out[i, :lens[i]] = j[key]
        return out

    hist = (pad("rewards", (nch,), np.float32), pad("keys", (2,), np.uint32),
            pad("contrib", (m,), np.float32), pad("aoi", (m,), np.float32),
            pad("served", (m,), np.int32),
            np.arange(length)[None, :] < np.asarray(lens)[:, None])
    params = tuple(sorted((k, v) for k, v in scheduler.items()
                          if isinstance(v, (int, float))))
    out = np.asarray(_compiled(params, dtype)(hist))
    return [out[i, :lens[i]] for i in range(len(jobs))]
