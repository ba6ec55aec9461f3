"""Marginal-contribution estimation (Sec. V, Eq. 32-35 and 41-43).

The Shapley value (Eq. 32) is approximated with the FedCE-style estimator
the paper adopts:

    C~_m = Gamma_cos * Gamma_err
    Gamma_cos = 1 - cos( grad F_m(w_t^m), grad F(w_t^{-m}) )      (Eq. 34)
    Gamma_err = E( D^_m ; w_t^{-m} )                              (Eq. 35)

where ``w^{-m}`` / ``grad F(w^{-m})`` are leave-one-out (LOO) aggregates.
Under non-stationary channels fresh client updates are not always
available, so the server keeps a *buffer* of the most recent gradient and
parameter vector per client (Eq. 41-42) and computes the LOO quantities
from it.  Aggregation weights are the normalized contributions (Eq. 43).

All functions operate on flattened gradient matrices ``(M, P)`` so the
same code serves the CIFAR-scale FL examples and the sharded LLM runtime
(where P is the per-shard parameter count and the cosine reduces over the
mesh via the surrounding pjit).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

_EPS = 1e-12


class ContributionBuffer(NamedTuple):
    """Server-side buffer (Eq. 41-42): last-known per-client grad + params."""

    grads: jnp.ndarray      # (M, P) buffered gradient vectors  nabla F~(w^m)
    params: jnp.ndarray     # (M, P) buffered parameter vectors w~_m
    fresh: jnp.ndarray      # (M,)   1.0 once a client has ever reported


def init_buffer(n_clients: int, n_params: int) -> ContributionBuffer:
    return ContributionBuffer(
        grads=jnp.zeros((n_clients, n_params), jnp.float32),
        params=jnp.zeros((n_clients, n_params), jnp.float32),
        fresh=jnp.zeros((n_clients,), jnp.float32),
    )


def update_buffer(
    buf: ContributionBuffer,
    success: jnp.ndarray,       # (M,) bool — clients whose upload arrived
    new_grads: jnp.ndarray,     # (M, P) this round's (possibly stale) updates
    new_params: jnp.ndarray,    # (M, P) the local params they were taken at
) -> ContributionBuffer:
    s = success.astype(jnp.float32)[:, None]
    return ContributionBuffer(
        grads=buf.grads * (1.0 - s) + new_grads * s,
        params=buf.params * (1.0 - s) + new_params * s,
        fresh=jnp.maximum(buf.fresh, success.astype(jnp.float32)),
    )


def _cosine(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    num = jnp.sum(a * b, axis=-1)
    den = jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1)
    return num / jnp.maximum(den, _EPS)


def loo_aggregates(buf: ContributionBuffer, weights: jnp.ndarray):
    """Leave-one-out weighted aggregates for every client at once.

    Eq. 41-42 with zeta-weights: for each m,
        g^{-m} = (sum_i zeta_i g_i - zeta_m g_m) / (1 - zeta_m)
    Returns (grads^{-m} (M, P), params^{-m} (M, P)).
    """
    w = (weights * buf.fresh)[:, None]                    # ignore never-seen clients
    wsum = jnp.maximum(jnp.sum(w), _EPS)
    g_tot = jnp.sum(w * buf.grads, axis=0, keepdims=True)
    p_tot = jnp.sum(w * buf.params, axis=0, keepdims=True)
    denom = jnp.maximum(wsum - w, _EPS)
    g_loo = (g_tot - w * buf.grads) / denom
    p_loo = (p_tot - w * buf.params) / denom
    return g_loo, p_loo


def marginal_contribution(
    buf: ContributionBuffer,
    weights: jnp.ndarray,
    proxy_loss_fn: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
) -> jnp.ndarray:
    """C~_m = Gamma_cos(m) * Gamma_err(m)  (Eq. 33).

    proxy_loss_fn: maps a flattened parameter vector to the server's proxy
    loss (Eq. 35), evaluated once per leave-one-out model (vmapped over
    the M rows).  That is M forwards of the model a round: at LLM scale
    with LoRA adapters on a frozen base it is deployable, and the
    benchmark's ``dsv2lite-fedlora`` cell runs it on DeepSeek-V2-Lite's
    first stage.  When None, Gamma_err = 1 and the estimator degrades
    gracefully to the cosine term.
    """
    g_loo, p_loo = loo_aggregates(buf, weights)
    gamma_cos = 1.0 - _cosine(buf.grads, g_loo)           # Eq. 34: in [0, 2]
    if proxy_loss_fn is not None:
        gamma_err = jax.vmap(proxy_loss_fn)(p_loo)        # Eq. 35
    else:
        gamma_err = jnp.ones_like(gamma_cos)
    contrib = gamma_cos * gamma_err
    # never-seen clients get the mean contribution (uninformative prior)
    seen = buf.fresh > 0.5
    fill = jnp.sum(jnp.where(seen, contrib, 0.0)) / jnp.maximum(jnp.sum(seen), 1.0)
    fill = jnp.where(jnp.any(seen), fill, 1.0)
    return jnp.where(seen, contrib, fill)


def aggregation_weights(contrib: jnp.ndarray) -> jnp.ndarray:
    """Eq. 43: zeta_m = C~_m / sum_l C~_l (clipped to be a valid simplex point)."""
    c = jnp.maximum(contrib, _EPS)
    return c / jnp.sum(c)


def exact_shapley(
    utility_fn: Callable[[jnp.ndarray], jnp.ndarray], n_clients: int
) -> jnp.ndarray:
    """Exact Shapley values (Eq. 32) by subset enumeration — O(2^M).

    ``utility_fn`` maps a (M,) 0/1 membership mask to the coalition's
    utility U(S).  Tractable for the paper's experiment scales (M <= ~16);
    used to validate the FedCE-style estimator (Eq. 33) against ground
    truth in tests/benchmarks, not in the runtime path.
    """
    import itertools
    import math

    m = n_clients
    values = jnp.zeros((m,))
    # cache utilities per subset bitmask
    utils = {}

    def u(mask_bits):
        if mask_bits not in utils:
            mask = jnp.array([(mask_bits >> i) & 1 for i in range(m)], jnp.float32)
            utils[mask_bits] = utility_fn(mask)
        return utils[mask_bits]

    fact = math.factorial
    for i in range(m):
        acc = 0.0
        others = [j for j in range(m) if j != i]
        for r in range(m):
            w = fact(r) * fact(m - r - 1) / fact(m)
            for subset in itertools.combinations(others, r):
                bits = sum(1 << j for j in subset)
                acc += w * float(u(bits | (1 << i)) - u(bits))
        values = values.at[i].set(acc)
    return values
