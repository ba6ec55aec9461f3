"""Public jit'd entry points for the Pallas kernels.

Every entry point takes a ``backend``: ``None`` (auto) runs the compiled
Pallas kernel on TPU and the pure-jnp oracle (``repro.kernels.ref``)
elsewhere; ``"pallas"`` demands the compiled kernel and raises off-TPU;
``"pallas_interpret"`` runs the kernel body under Pallas' interpreter (the
kernel-semantics tests on CPU); ``"jnp"`` runs the oracle.  Interpret mode
happens only when it is asked for.  Models and the FL runtime call these
wrappers, never the kernels directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import client_gather as _cg
from repro.kernels import flash_attention as _fa
from repro.kernels import glr_scan as _glr
from repro.kernels import glr_step as _gs
from repro.kernels import robust_agg as _ra
from repro.kernels import weighted_aggregate as _wa
from repro.kernels import ref as ref  # re-export the oracles


_GLR_BACKENDS = ("pallas", "pallas_interpret", "jnp")


def _auto(backend: str | None) -> str:
    if backend is None:
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


def _interpret(backend: str, name: str) -> bool:
    """The ``interpret`` flag of a Pallas backend.  ``"pallas"`` is the
    compiled kernel, which only a TPU can run: asking for it elsewhere is an
    error, never a silent switch to the interpreter."""
    if backend == "pallas_interpret":
        return True
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"{name}: backend='pallas' needs a TPU, but JAX is running on "
            f"{jax.default_backend()!r}; use 'pallas_interpret' for the "
            f"interpreter or 'jnp' for the oracle")
    return False


def glr_scan(
    hist: jnp.ndarray, counts: jnp.ndarray, backend: str | None = None
) -> jnp.ndarray:
    """GLR change-point statistic per channel.  hist (N, H), counts (N,) -> (N,).

    This runs inside every step of the simulation scan (the GLR-CUCB
    detector), so the dispatch matters: on TPU the Pallas kernel is the fast
    path, but on CPU Pallas only has interpret mode — a Python-built
    emulation graph that is far slower than plain XLA.  Backends:

      None               auto: "pallas" on TPU, "jnp" elsewhere (the hot-path
                         default used by ``GLRCUCB.update``)
      "pallas"           compiled Pallas kernel (raises off-TPU)
      "pallas_interpret" Pallas kernel in interpret mode (kernel semantics
                         tests)
      "jnp"              the pure-jnp oracle in ``repro.kernels.ref``

    All backends implement identical semantics; tests assert the pallas and
    jnp paths agree inside a jitted ``GLRCUCB.update``.
    """
    backend = _auto(backend)
    if backend == "jnp":
        return ref.glr_scan(hist, counts)
    if backend in ("pallas", "pallas_interpret"):
        return _glr.glr_scan(hist, counts,
                             interpret=_interpret(backend, "glr_scan"))
    raise ValueError(f"glr_scan: unknown backend {backend!r}; use one of {_GLR_BACKENDS}")


_GLR_SPLIT_GRIDS = ("all", "geometric")


def glr_step(cum, total, base, counts, r_vec, sched,
             split_grid: str = "all", backend: str | None = None):
    """Fused streaming GLR detector step (prefix append + test).

    Per channel: masked append of ``r_vec`` (where ``sched``) into the
    carried prefix-sum state (``cum``/``total``/``base`` — see
    ``repro.kernels.ref.glr_stream_append``; raw samples are never
    materialized), and the GLR statistic over the post-append window, with
    no cumsum anywhere.  Returns ``(cum, total, base, stats)``.

    ``split_grid``:
      "all"        every split point 1 <= s <= n-1 (the dense reference grid)
      "geometric"  only splits at power-of-two distances from either window
                   end — O(log H) evaluated splits per test instead of O(H)

    ``backend`` follows the ``glr_scan`` dispatch policy (this runs inside
    the GLR-CUCB scan body on every detection round):

      None               auto: "pallas" on TPU, "jnp" elsewhere (the hot-path
                         default used by ``GLRCUCB.update``)
      "pallas"           compiled fused Pallas kernel (raises off-TPU)
      "pallas_interpret" Pallas kernel in interpret mode (kernel semantics
                         tests)
      "jnp"              the pure-jnp oracle in ``repro.kernels.ref`` (the
                         geometric grid gathers its O(log H) splits there;
                         the Pallas kernel masks the same set densely — the
                         split sets coincide, so the sup agrees)

    Inputs may carry a leading tenant axis — ``cum (G, N, H)``, everything
    else ``(G, N)`` — in which case every backend evaluates all G tenants'
    steps at once (the Pallas paths as ONE ``glr_step_tenants`` launch with
    tenants on the leading grid axis).  The 2-D Pallas paths go through
    ``vmappable_glr_step``, whose ``custom_vmap`` rule lowers an outer
    ``vmap`` (the serving loop's tenant axis) to that same tenant kernel.
    """
    if split_grid not in _GLR_SPLIT_GRIDS:
        raise ValueError(
            f"glr_step: unknown split_grid {split_grid!r}; "
            f"use one of {_GLR_SPLIT_GRIDS}")
    backend = _auto(backend)
    tenants = jnp.ndim(cum) == 3
    if backend == "jnp":
        if tenants:
            return jax.vmap(
                functools.partial(ref.glr_step, split_grid=split_grid)
            )(cum, total, base, counts, r_vec, sched)
        return ref.glr_step(cum, total, base, counts, r_vec, sched,
                            split_grid=split_grid)
    if backend in ("pallas", "pallas_interpret"):
        interpret = _interpret(backend, "glr_step")
        if tenants:
            return _gs.glr_step_tenants(cum, total, base, counts, r_vec,
                                        sched, split_grid=split_grid,
                                        interpret=interpret)
        return _gs.vmappable_glr_step(split_grid, interpret)(
            cum, total, base, counts, r_vec, sched)
    raise ValueError(
        f"glr_step: unknown backend {backend!r}; use one of {_GLR_BACKENDS}")


_WA_BACKENDS = ("pallas", "pallas_interpret", "jnp")


def weighted_aggregate(
    updates: jnp.ndarray, scale: jnp.ndarray, backend: str | None = None
) -> jnp.ndarray:
    """Eq. 7 fused masked aggregation.  updates (M, P), scale (M,) -> (P,) f32.

    Runs inside every round of the scan-fused FL trainer, so the dispatch
    follows the same policy as ``glr_scan``: Pallas interpret mode is never
    auto-selected on the hot path.  On CPU this matters twice over — the
    interpret-mode kernel is a Python-built emulation, and its ``vmap``
    lowering under the batched FL engine (``repro.sim.simulate_fl_batch``)
    devolves into per-batch-element emulated grids (measured ~150x slower
    than the serial jnp path at batch 8).  Backends:

      None               auto: "pallas" on TPU, "jnp" elsewhere
      "pallas"           compiled Pallas kernel (raises off-TPU)
      "pallas_interpret" Pallas kernel in interpret mode (tests)
      "jnp"              the pure-jnp oracle in ``repro.kernels.ref``
    """
    backend = _auto(backend)
    if backend == "jnp":
        return ref.weighted_aggregate(updates, scale)
    if backend in ("pallas", "pallas_interpret"):
        return _wa.weighted_aggregate(
            updates, scale,
            interpret=_interpret(backend, "weighted_aggregate"))
    raise ValueError(
        f"weighted_aggregate: unknown backend {backend!r}; use one of {_WA_BACKENDS}")


_RT_BACKENDS = ("pallas", "pallas_interpret", "jnp")


def robust_trimmed(
    updates: jnp.ndarray,
    mask: jnp.ndarray,
    n_succ: jnp.ndarray,
    k_trim: jnp.ndarray,
    backend: str | None = None,
) -> jnp.ndarray:
    """Masked per-coordinate trimmed mean / median.

    updates (M, P), mask (M,) {0,1}, n_succ scalar participant count,
    k_trim scalar trim depth -> (P,) f32.  ``k_trim = floor((n-1)/2)``
    yields the coordinate-wise median; zeros when nothing participates.
    Backs the robust aggregator families in ``repro.core.aggregation`` and
    runs inside the scan-fused FL round, so the dispatch follows the
    ``weighted_aggregate`` policy (Pallas interpret mode is never
    auto-selected on the hot path):

      None               auto: "pallas" on TPU, "jnp" elsewhere
      "pallas"           compiled Pallas kernel (raises off-TPU)
      "pallas_interpret" Pallas kernel in interpret mode (tests)
      "jnp"              the pure-jnp oracle in ``repro.kernels.ref``
    """
    backend = _auto(backend)
    if backend == "jnp":
        return ref.robust_trimmed(updates, mask, n_succ, k_trim)
    if backend in ("pallas", "pallas_interpret"):
        return _ra.robust_trimmed(
            updates, mask, n_succ, k_trim,
            interpret=_interpret(backend, "robust_trimmed"))
    raise ValueError(
        f"robust_trimmed: unknown backend {backend!r}; use one of {_RT_BACKENDS}")


_CG_BACKENDS = ("pallas", "pallas_interpret", "jnp")


def client_gather(
    client_x: jnp.ndarray, ids: jnp.ndarray, backend: str = "jnp"
) -> jnp.ndarray:
    """Rows ``ids`` (M,) of a per-client array: (N, n, d) or (N, n) ->
    (M, n, d) or (M, n), the same bytes on every backend.

    The kernel reads a dataset stored client-minor (the client axis on the
    lanes, as a TPU lays out (N, n, d) uint8 when d pads worse than N) in
    place, where an XLA gather inside a loop first relayouts all of it.  On
    any other layout the kernel's transposed view would itself be that
    copy, and the layout is seen only outside ``jit``, so the kernel is
    never auto-selected: the caller that holds the array names it
    (``repro.data.pipeline.gather_backend``).  Backends:

      "jnp"              ``jnp.take`` (``repro.kernels.ref``), the default
      "pallas"           compiled Pallas kernel (raises off-TPU)
      "pallas_interpret" Pallas kernel in interpret mode (tests)

    What shapes and dtypes the kernel reads: ``client_gather.supports``.
    """
    if backend == "jnp":
        return ref.client_gather(client_x, ids)
    if backend in ("pallas", "pallas_interpret"):
        return _cg.vmappable_client_gather(
            _interpret(backend, "client_gather"))(client_x, ids)
    raise ValueError(
        f"client_gather: unknown backend {backend!r}; use one of {_CG_BACKENDS}")


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    backend: str = "pallas",
) -> jnp.ndarray:
    """Blockwise GQA attention.  q (B,Hq,S,D), k/v (B,Hkv,S,D) -> (B,Hq,S,D).

    Pads the head dim to a 128-lane multiple (zero-padded dims contribute
    nothing to q.k^T or the weighted value sum, so the result is exact) and
    picks MXU-aligned default tile sizes.  ``backend`` is ``"pallas"`` (the
    compiled kernel, TPU only) or ``"pallas_interpret"``; the oracle is
    ``ref.mha_attention``.
    """
    if backend not in ("pallas", "pallas_interpret"):
        raise ValueError(
            f"flash_attention: unknown backend {backend!r}; use 'pallas' or "
            "'pallas_interpret'")
    d = q.shape[-1]
    scale = float(scale) if scale is not None else float(1.0 / (d ** 0.5))
    d_pad = (-d) % 128
    if d_pad:
        pad = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    s = q.shape[2]
    bq = block_q or min(_fa.DEFAULT_BLOCK_Q, max(8, s))
    bk = block_k or min(_fa.DEFAULT_BLOCK_K, max(8, s))
    out = _fa.flash_attention(
        q, k, v,
        causal=causal, window=window, scale=scale,
        block_q=bq, block_k=bk,
        interpret=_interpret(backend, "flash_attention"),
    )
    return out[..., :d]
