"""Client-side local training (Step 2, Eq. 5).

``local_sgd`` runs E mini-batch SGD steps from the received global model
and returns the *cumulative update*  G~ = (w^0 - w^E) / eta  (Eq. 6).
The function is pure so the server runtime vmaps it over all clients —
one FL round (all clients' local epochs included) is a single XLA program.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp


def local_sgd(
    loss_fn: Callable,
    params: Any,
    batches_x: jnp.ndarray,     # (E, B, ...)
    batches_y: jnp.ndarray,     # (E, B)
    lr: float,
):
    """Returns (cumulative_update G~ [same pytree as params], final local loss).

    A ``loss_fn`` that returns ``(loss, counters)`` gets its counters,
    summed over the E steps, back third."""

    def loss_aux(w, x, y):
        out = loss_fn(w, x, y)
        return out if isinstance(out, tuple) else (out, None)

    grad_fn = jax.value_and_grad(loss_aux, has_aux=True)

    def step(w, batch):
        x, y = batch
        out, g = grad_fn(w, x, y)
        w = jax.tree_util.tree_map(lambda p, gi: p - lr * gi, w, g)
        return w, out

    w_final, (losses, aux) = jax.lax.scan(step, params, (batches_x, batches_y))
    g_tilde = jax.tree_util.tree_map(
        lambda w0, we: (w0 - we) / lr, params, w_final)
    if aux is None:
        return g_tilde, losses[-1]
    return g_tilde, losses[-1], jax.tree_util.tree_map(
        lambda a: jnp.sum(a, axis=0), aux)
