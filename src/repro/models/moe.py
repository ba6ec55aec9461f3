"""Mixture-of-Experts FFN (deepseek-v2: 2 shared + 160 routed top-6;
dbrx: 16 routed top-4).

Dispatch is *sort-based with static capacity* — the TPU-native layout:

1. router scores -> top-k expert ids + normalized weights per token;
2. flatten (token, k) assignments, ``argsort`` by expert id (static shape);
3. scatter tokens into an (E, C, d) buffer (C = capacity per expert —
   tokens beyond capacity are dropped, the standard GShard semantics);
4. one batched einsum per FFN matrix: (E, C, d) x (E, d, f) — the expert
   dim rides the ``expert`` logical axis so GSPMD turns the dispatch
   scatter/gather into all-to-alls across the expert-parallel shards;
5. gather results back to token order and combine with router weights.

A load-balance auxiliary loss (mean router prob x token fraction per
expert) is returned for the trainer.  All shapes static -> dry-run safe.

``held_moe_ffn`` is the dropless layer of one expert-parallel device
(``capacity_factor`` 0): it holds experts ``[held_first, held_first +
n_held)`` of ``n_experts`` (by default all of them), routes every token
over all ``n_experts``, and computes only its held experts' part of the
result, every routed token kept.  The assignments are sorted by expert and
the held groups run as one grouped matmul (``jax.lax.ragged_dot``: on a
TPU the ``ragged-dot`` kernel, whose tiles stop at the last held row).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.custom_derivatives import SymbolicZero

from repro.configs.base import ModelConfig
from repro.models.layers import ParamBuilder


def add_moe_params(pb: ParamBuilder, prefix: str, cfg: ModelConfig, stacked: int = 0):
    d, e, h = cfg.d_model, cfg.n_experts, cfg.n_held_experts
    fe = cfg.d_expert or cfg.d_ff
    lead = (stacked,) if stacked else ()
    ls = ("layers",) if stacked else ()
    pb.add(f"{prefix}/router", lead + (d, e), ls + ("embed", None), scale=0.02)
    pb.add(f"{prefix}/w_gate", lead + (h, d, fe), ls + ("expert", "embed", None))
    pb.add(f"{prefix}/w_up", lead + (h, d, fe), ls + ("expert", "embed", None))
    pb.add(f"{prefix}/w_down", lead + (h, fe, d), ls + ("expert", None, "embed"))
    if cfg.n_shared_experts:
        fs = fe * cfg.n_shared_experts
        pb.add(f"{prefix}/ws_gate", lead + (d, fs), ls + ("embed", "heads"))
        pb.add(f"{prefix}/ws_up", lead + (d, fs), ls + ("embed", "heads"))
        pb.add(f"{prefix}/ws_down", lead + (fs, d), ls + ("heads", "embed"))


def route(x: jnp.ndarray, router: jnp.ndarray, cfg: ModelConfig):
    """Softmax router over all ``n_experts`` in float32 and its greedy top-k:
    ``(probs (..., E), topw (..., k), topi (..., k))``.  The top-k weights
    are renormalised to sum to one where ``norm_topk_prob``, else scaled by
    ``routed_scaling_factor`` (DeepSeek-V2's ``MoEGate``)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)
    elif cfg.routed_scaling_factor != 1.0:
        topw = topw * cfg.routed_scaling_factor
    return probs, topw, topi


def balance_loss(probs, topi, e: int):
    """Switch-style load-balance loss: mean router prob x token fraction."""
    k = topi.shape[-1]
    lead = tuple(range(probs.ndim - 1))
    me = jnp.mean(probs, axis=lead)
    hits = jnp.mean(jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32),
                            axis=-2), axis=lead) / k
    return jnp.sum(me * hits) * e


def _dispatch_one(xt, topi, topw, e: int, k: int, cap: int):
    """Sort-based dispatch for one batch row.  xt (T,d), topi/topw (T,k).

    Returns (buf (E, C, d), t_sorted, slot, keep_w) for the combine step.
    Row-local so the argsort never crosses the batch sharding — a global
    token sort would force an all-gather of every token on every device
    (hundreds of GB at 1M tokens).
    """
    t, d = xt.shape
    flat_e = topi.reshape(-1)                               # (t*k,)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_w = topw.reshape(-1)
    order = jnp.argsort(flat_e)
    e_sorted = flat_e[order]
    t_sorted = flat_t[order]
    w_sorted = flat_w[order]
    group_start = jnp.searchsorted(e_sorted, jnp.arange(e))
    pos_in_e = jnp.arange(t * k) - group_start[e_sorted]
    keep = pos_in_e < cap                                   # capacity drop
    slot = jnp.where(keep, e_sorted * cap + pos_in_e, e * cap)  # OOB sentinel
    buf = jnp.zeros((e * cap + 1, d), xt.dtype).at[slot].set(xt[t_sorted])
    return buf[:-1].reshape(e, cap, d), t_sorted, slot, jnp.where(keep, w_sorted, 0.0)


def moe_ffn(
    p: Dict[str, jnp.ndarray], prefix: str, x: jnp.ndarray, cfg: ModelConfig,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Routing/dispatch is vmapped over the batch rows (capacity enforced per
    row) so the token axis stays data-sharded; the expert axis rides the
    'expert' logical axis -> tensor shards.
    """
    from repro.models.act_sharding import constrain

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = max(int(s * k * cfg.capacity_factor / e), 1)

    if cfg.n_held_experts != e:
        raise ValueError("a capacity-bound MoE layer holds every expert; "
                         "hold a share with capacity_factor 0 (held_moe_ffn)")
    probs, topw, topi = route(x, p[f"{prefix}/router"], cfg)
    aux = balance_loss(probs, topi, e)

    # ---- per-row sort-based dispatch (vmapped) ------------------------------
    # §Perf note: an explicit batched rewrite with expert-dim sharding
    # constraints was tried and REFUTED — constraining a tensor written via
    # a data-dependent scatter forces a resharding storm (1.7 TB/device of
    # collectives vs 254 GB for this form); GSPMD's own placement of the
    # vmapped dispatch is the best measured layout.
    buf, t_sorted, slot, keep_w = jax.vmap(
        lambda xr, ir, wr: _dispatch_one(xr, ir, wr, e, k, cap)
    )(x, topi, topw)                                        # buf (B, E, C, d)

    # ---- expert FFN (batched over batch x expert) ---------------------------
    g = jnp.einsum("becd,edf->becf", buf, p[f"{prefix}/w_gate"])
    u = jnp.einsum("becd,edf->becf", buf, p[f"{prefix}/w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y_buf = jnp.einsum("becf,efd->becd", h, p[f"{prefix}/w_down"])
    y_flat = y_buf.reshape(b, e * cap, d)

    # ---- combine back in token order ----------------------------------------
    def combine_one(yf, t_s, sl, kw):
        contrib = kw[:, None] * yf[jnp.clip(sl, 0, e * cap - 1)].astype(jnp.float32)
        return jnp.zeros((s, d), jnp.float32).at[t_s].add(contrib)

    out = jax.vmap(combine_one)(y_flat, t_sorted, slot, keep_w)
    out = constrain(out, "batch", None, None).astype(x.dtype)

    # ---- shared experts (always-on path) -------------------------------------
    if cfg.n_shared_experts:
        sg = jnp.einsum("bsd,df->bsf", x, p[f"{prefix}/ws_gate"])
        su = jnp.einsum("bsd,df->bsf", x, p[f"{prefix}/ws_up"])
        sh = jax.nn.silu(sg.astype(jnp.float32)).astype(x.dtype) * su
        out = out + jnp.einsum("bsf,fd->bsd", sh, p[f"{prefix}/ws_down"])

    return out, aux


# ---------------------------------------------------------------------------
# dropless held-experts layer (expert parallelism's local share)
# ---------------------------------------------------------------------------

def _member_loop(f, axis_size, in_batched, *args):
    """A ``custom_vmap`` rule that runs ``f`` once per batch member
    (``lax.map``), the unbatched arguments shared."""
    def one(batched):
        it = iter(batched)
        return f(*[next(it) if b else a for a, b in zip(args, in_batched)])

    out = jax.lax.map(one, [a for a, b in zip(args, in_batched) if b])
    return out, jax.tree_util.tree_map(lambda _: True, out)


def _per_member(fn):
    """``fn`` under ``vmap`` as a loop over the batch: for a function of
    ``ragged_dot``, which has no batching rule, whose members cannot share
    one grouped matmul."""
    f = custom_vmap(fn)
    f.def_vmap(functools.partial(_member_loop, f))
    return f


def _tokenwise(fn, n_tok: int):
    """``fn`` batched by folding: its first ``n_tok`` arguments carry a
    leading token axis and each output row belongs to one token, so a
    ``vmap`` over any of them is the same call on the folded tokens (one
    grouped matmul for all clients or models of a vmapped round, where a
    ``vmap`` of ``ragged_dot`` has no rule).  The other arguments (the
    experts' weights) are shared by the whole batch; where they are
    batched too, each member runs on its own (``_member_loop``)."""
    f = custom_vmap(fn)

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        if any(in_batched[n_tok:]):
            return _member_loop(f, axis_size, in_batched, *args)
        tok = [a.reshape((-1,) + a.shape[2:]) if b
               else jnp.broadcast_to(a, (axis_size,) + a.shape).reshape(
                   (-1,) + a.shape[1:])
               for a, b in zip(args[:n_tok], in_batched[:n_tok])]
        out = jax.tree_util.tree_map(
            lambda o: o.reshape((axis_size, -1) + o.shape[1:]),
            f(*tok, *args[n_tok:]))
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return f


def _held_sort(topi, h: int, first: int):
    """The assignments sorted by held expert (absent ones last): the order,
    each sorted row's token, the (H,) group sizes and which rows are held."""
    t, k = topi.shape
    local = topi.reshape(-1) - first
    held = (local >= 0) & (local < h)
    group = jnp.where(held, local, h)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((h + 1,), jnp.int32).at[group].add(1)[:h]
    valid = jnp.arange(t * k) < jnp.sum(sizes)
    return order, order // k, sizes, valid


def _held_rows(x, topi, topw, w_gate, w_up, w_down, first):
    """The held experts' part of the routed output, (T, d) in ``x``'s dtype:
    ``sum_j topw[t, j] * FFN_{topi[t, j]}(x[t])`` over the assignments whose
    expert ``topi`` is held; and, for the backward pass, the sorted rows'
    gate and up projections and expert outputs.  The held groups run
    through ``ragged_dot``.  Its rows past the last held one are left
    unwritten (on a TPU, whatever the buffer held), so every row-wise
    result is masked to the held rows."""
    t, k = topi.shape
    order, tok, sizes, valid = _held_sort(topi, w_gate.shape[0], first)
    xs = jnp.where(valid[:, None], x[tok], 0)
    g = jax.lax.ragged_dot(xs, w_gate, sizes)
    u = jax.lax.ragged_dot(xs, w_up, sizes)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.where(valid[:, None], jax.lax.ragged_dot(act, w_down, sizes), 0)
    wts = jnp.where(valid, topw.reshape(-1)[order], 0.0)
    out = jnp.zeros((t, x.shape[1]), jnp.float32).at[tok].add(
        wts[:, None] * y.astype(jnp.float32))
    return out.astype(x.dtype), g, u, y


def _ragged_t(cot, w, sizes, d_in: int):
    """The transpose of ``ragged_dot(., w, sizes)`` applied to ``cot``."""
    like = jax.ShapeDtypeStruct((cot.shape[0], d_in), cot.dtype)
    return jax.linear_transpose(
        lambda a: jax.lax.ragged_dot(a, w, sizes), like)(cot)[0]


def _ragged_wt(lhs, cot, w, sizes):
    """The transpose of ``ragged_dot(lhs, ., sizes)`` applied to ``cot``:
    each group's ``lhs`` rows times its ``cot`` rows, (H, ...) like ``w``."""
    like = jax.ShapeDtypeStruct(w.shape, w.dtype)
    return jax.linear_transpose(
        lambda v: jax.lax.ragged_dot(lhs, v, sizes), like)(cot)[0]


def _held_rows_bwd(topi, topw, g, u, y, dout, w_gate, w_up, w_down, first,
                   x=None):
    """Cotangents of ``_held_rows``'s output for its input and ``topw``,
    from the rows its forward kept (three grouped matmuls, no recompute);
    given the input ``x``, also for the three weights (three more)."""
    t, k = topi.shape
    d = dout.shape[1]
    order, tok, sizes, valid = _held_sort(topi, w_gate.shape[0], first)
    rows = valid[:, None]
    dout_rows = dout[tok].astype(jnp.float32)
    wts = jnp.where(valid, topw.reshape(-1)[order], 0.0)
    dwts = jnp.where(valid, jnp.sum(dout_rows * y.astype(jnp.float32), -1), 0)
    dtopw = jnp.zeros((t * k,), jnp.float32).at[order].set(dwts).reshape(t, k)
    dy = (wts[:, None] * dout_rows).astype(y.dtype)
    dact = jnp.where(rows, _ragged_t(dy, w_down, sizes, g.shape[1]), 0)
    gf = jnp.where(rows, g, 0).astype(jnp.float32)
    sig = jax.nn.sigmoid(gf)
    dact = dact.astype(jnp.float32)
    du = (dact * gf * sig).astype(u.dtype)
    dg = (dact * u.astype(jnp.float32) * sig * (1 + gf * (1 - sig))).astype(g.dtype)
    dxs = _ragged_t(dg, w_gate, sizes, d) + _ragged_t(du, w_up, sizes, d)
    dxs = jnp.where(rows, dxs.astype(jnp.float32), 0)
    dx = (jnp.zeros((t, d), jnp.float32).at[tok].add(dxs).astype(dout.dtype),
          dtopw.astype(topw.dtype))
    if x is None:
        return dx
    xs = jnp.where(rows, x[tok], 0)
    act = (gf * sig).astype(u.dtype) * jnp.where(rows, u, 0)
    return dx + (_ragged_wt(xs, dg, w_gate, sizes),
                 _ragged_wt(xs, du, w_up, sizes),
                 _ragged_wt(act, dy, w_down, sizes))


def _held_grads(x, topi, topw, dout, w_gate, w_up, w_down, first):
    """Every cotangent of ``_held_rows``'s output, the forward recomputed:
    for ``x``, ``topw`` and the three weights."""
    _, g, u, y = _held_rows(x, topi, topw, w_gate, w_up, w_down, first)
    return _held_rows_bwd(topi, topw, g, u, y, dout, w_gate, w_up, w_down,
                          first, x=x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def held_experts(x, topi, topw, w_gate, w_up, w_down, first: int):
    """``_held_rows``'s output for (T, d) tokens, batched by folding
    (``_tokenwise``).  Gradients reach ``x`` and ``topw`` from the rows the
    forward kept; the weights take theirs only where they are
    differentiated (full-parameter training), from a recomputed forward
    run per batch member, so that a frozen base (LoRA) costs nothing."""
    return _tokenwise(functools.partial(_held_rows, first=first), 3)(
        x, topi, topw, w_gate, w_up, w_down)[0]


def _held_fwd(x, topi, topw, w_gate, w_up, w_down, first):
    # symbolic zeros: each argument comes with whether it is differentiated;
    # the input is kept for the weights' cotangents only where they are
    args = [a.value for a in (x, topi, topw, w_gate, w_up, w_down)]
    out, g, u, y = _tokenwise(functools.partial(_held_rows, first=first), 3)(
        *args)
    trained = any(w.perturbed for w in (w_gate, w_up, w_down))
    return out, (args[1:], g, u, y, args[0] if trained else None)


def _held_bwd(first, res, dout):
    (topi, topw, w_gate, w_up, w_down), g, u, y, x = res
    if isinstance(dout, SymbolicZero):
        dout = jnp.zeros(dout.shape, dout.dtype)
    if x is not None:
        dx, dtopw, dwg, dwu, dwd = _per_member(
            functools.partial(_held_grads, first=first))(
                x, topi, topw, dout, w_gate, w_up, w_down)
        return dx, None, dtopw, dwg, dwu, dwd
    dx, dtopw = _tokenwise(functools.partial(_held_rows_bwd, first=first), 6)(
        topi, topw, g, u, y, dout, w_gate, w_up, w_down)
    return dx, None, dtopw, None, None, None


held_experts.defvjp(_held_fwd, _held_bwd, symbolic_zeros=True)


def held_moe_ffn(
    p: Dict[str, jnp.ndarray], prefix: str, x: jnp.ndarray, cfg: ModelConfig,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Dropless MoE FFN of the device holding experts ``[held_first,
    held_first + n_held_experts)``.  x: (B, S, d) -> (out (B, S, d), stats).

    Every token is routed over all ``n_experts`` and its assignments to held
    experts are all computed; what the absent experts would add is left out
    (it is another device's share), and the shared experts, which every
    device holds, are added whole.  ``stats``: ``balance`` (the load-balance
    loss), ``routed`` (H,) (assignments per held expert), ``absent``
    (assignments to experts held elsewhere) and ``dropped`` (assignments to
    held experts left out: 0, as the layer has no capacity).
    """
    b, s, d = x.shape
    e, k, h = cfg.n_experts, cfg.experts_per_token, cfg.n_held_experts
    probs, topw, topi = route(x, p[f"{prefix}/router"], cfg)
    routed = jnp.sum(jax.nn.one_hot(topi - cfg.held_first, h, dtype=jnp.int32),
                     axis=(0, 1, 2))
    out = held_experts(
        x.reshape(b * s, d), topi.reshape(b * s, k), topw.reshape(b * s, k),
        p[f"{prefix}/w_gate"], p[f"{prefix}/w_up"], p[f"{prefix}/w_down"],
        cfg.held_first).reshape(b, s, d)
    if cfg.n_shared_experts:
        sg_ = jnp.einsum("bsd,df->bsf", x, p[f"{prefix}/ws_gate"])
        su = jnp.einsum("bsd,df->bsf", x, p[f"{prefix}/ws_up"])
        sh = jax.nn.silu(sg_.astype(jnp.float32)).astype(x.dtype) * su
        out = out + jnp.einsum("bsf,fd->bsd", sh, p[f"{prefix}/ws_down"])
    stats = {"balance": balance_loss(probs, topi, e), "routed": routed,
             "absent": b * s * k - jnp.sum(routed),
             "dropped": jnp.zeros((), jnp.int32)}
    return out, stats
