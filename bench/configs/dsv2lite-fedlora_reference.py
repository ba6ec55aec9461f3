"""Plain reference for the ``dsv2lite-fedlora`` deployment: federated LoRA
fine-tuning of DeepSeek-V2-Lite's first layers, and the first rounds of
population-scale asynchronous FL over them.

The model follows DeepSeek-V2 (arXiv:2405.04434) and the published
``modeling_deepseek.py`` of DeepSeek-V2-Lite, written out plainly:

* MLA without a query low-rank: ``q = x Wq`` split into 128 "nope" and 64
  rotary dims a head; ``[c, k_pe] = x Wkv_down``, ``c`` (512) RMS-normed
  and lifted by ``Wkv_up`` to each head's 128 nope key dims and 128 value
  dims; ``k_pe`` one rotary key shared by the heads.  Rotary dims pair
  interleaved, as ``apply_rotary_pos_emb`` reads them (de-interleaved,
  then rotated by halves), at YaRN's inverse frequencies
  (``DeepseekV2YarnRotaryEmbedding``); the softmax scale is
  ``192^-0.5 * mscale(factor, mscale_all_dim)^2``
  (``yarn_get_mscale``); causal.
* layer 0: a SwiGLU MLP 10944 wide; later layers: a softmax router over
  all 64 experts (float32), greedy top-6, weights left unnormalised
  (``norm_topk_prob`` false) and scaled by ``routed_scaling_factor``; the
  routed experts held here (a contiguous share of the 64) as a loop, one
  plain SwiGLU of width 1408 an expert over every token, weighted by the
  token's router weight for it (0 where it did not pick it); and the 2
  shared experts as one SwiGLU of width 2816.  Absent experts add nothing.
* pre-norm residual blocks (RMSNorm), a final RMSNorm, an unembedding over
  the vocabulary slice and next-token cross-entropy, averaged.
* LoRA (Hu et al., arXiv:2106.09685) on ``wq``, ``wkv_down``, ``wkv_up``
  and ``wo``: ``x W + (alpha / r) (x A) B``, the base frozen.

The weights are drawn here (``init``): the bfloat16 base, which stands
for the deployment's checkpoint, and the float32 adapters, in a flat dict
(``layers/00/b/...`` for the dense layer, ``blocks/b/...`` stacked for
the MoE layers, ``<weight>/lora_a`` / ``<weight>/lora_b`` for the
adapters).  That layout is the checkpoint's format, which the program is
given as data; it is read here by name.

``replay`` follows the trainer's first rounds from the same seed, inputs
and settings, as ``fedcnn-pop_reference.py`` does for the CNN (the round
protocol of arXiv:2503.01324, Alg. 1 with Sec. V, at population scale),
with the adapters as the trained model and computed in blocks: the
clients' local steps one client at a time, the leave-one-out proxy losses
one model and a few sequences at a time.  Handed the program's
contribution estimate after a round, it goes on from that estimate and
reports its own for comparison.

It imports nothing of the program.  ``dtype`` sets the precision of
everything it computes: float32 with every matmul at
``precision="highest"``, from the bfloat16 base weights as stored, is the
reference; bfloat16 throughout (LoRA factors, activations, FL state) is
the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

DATA_TAG = 0xDA7A      # the round key's data stream
AVAIL_TAG = 0xA7A1     # the round key's availability stream
IDLE, DROPPED = 0, 2
TARGETS = ("wq", "wkv_down", "wkv_up", "wo")


# --------------------------------------------------------------- the model
def model_of(cfg):
    """The model's settings from the configuration file (its keys are the
    published ``config.json``'s, with this deployment's share)."""
    return {
        "layers": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
        "qk_nope": cfg["qk_nope_head_dim"], "qk_rope": cfg["qk_rope_head_dim"],
        "v_head": cfg["v_head_dim"], "kv_lora_rank": cfg["kv_lora_rank"],
        "rope_theta": cfg["rope_theta"], "rope_scaling": cfg["rope_scaling"],
        "norm_eps": cfg["rms_norm_eps"],
        "first_k_dense": cfg["first_k_dense_replace"],
        "experts_per_token": cfg["num_experts_per_tok"],
        "norm_topk_prob": cfg["norm_topk_prob"],
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "held_first": cfg["held_experts"]["first"],
        "lora_rank": cfg["lora"]["rank"], "lora_alpha": cfg["lora"]["alpha"]}


def shapes(cfg):
    """The base's flat-dict layout: ``{path: (shape, init)}``, ``init``
    ``"ones"`` for a norm's gain, else the normal draw's std."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, fe = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    v, k0 = cfg["vocab_size"], cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - k0

    def w(*shape):
        return shape, shape[-2] ** -0.5          # std 1/sqrt(fan_in)

    def block(lead, moe):
        out = {"norm1": (lead + (d,), "ones"), "norm2": (lead + (d,), "ones"),
               "attn/kv_norm": (lead + (r,), "ones"),
               "attn/wq": w(*lead, d, h * (dn + dr)),
               "attn/wkv_down": w(*lead, d, r + dr),
               "attn/wkv_up": w(*lead, r, h * (dn + dv)),
               "attn/wo": w(*lead, h * dv, d)}
        if not moe:
            f = cfg["intermediate_size"]
            out.update({"mlp/w_gate": w(*lead, d, f), "mlp/w_up": w(*lead, d, f),
                        "mlp/w_down": w(*lead, f, d)})
            return out
        e, fs = cfg["n_routed_experts"], fe * cfg["n_shared_experts"]
        out.update({
            "moe/router": (lead + (d, cfg["held_experts"]["routed_over"]), 0.02),
            "moe/w_gate": w(*lead, e, d, fe), "moe/w_up": w(*lead, e, d, fe),
            "moe/w_down": w(*lead, e, fe, d),
            "moe/ws_gate": w(*lead, d, fs), "moe/ws_up": w(*lead, d, fs),
            "moe/ws_down": w(*lead, fs, d)})
        return out

    layout = {"embed": ((v, d), 0.02), "unembed": w(d, v),
              "final_norm": ((d,), "ones")}
    for i in range(k0):
        layout.update({f"layers/{i:02d}/b/{k}": s
                       for k, s in block((), False).items()})
    if n_moe:
        layout.update({f"blocks/b/{k}": s
                       for k, s in block((n_moe,), True).items()})
    return layout


def init(cfg, base_key, key):
    """Seeded weights: the bfloat16 base from ``base_key`` (normal, std
    1/sqrt(fan_in); the embedding and router 0.02; norm gains 1) and the
    float32 LoRA adapters of every target weight from ``key``, ``A``
    drawn with std 1/sqrt(d_in) and ``B`` with the configuration's
    ``b_std``, both non-zero.  Returns ``(base, adapters)``."""
    base, adapters = {}, {}
    for i, (path, (shape, std)) in enumerate(sorted(shapes(cfg).items())):
        if std == "ones":
            base[path] = jnp.ones(shape, jnp.bfloat16)
        else:
            base[path] = (jax.random.normal(jax.random.fold_in(base_key, i),
                                            shape) * std).astype(jnp.bfloat16)
    r, b_std = cfg["lora"]["rank"], cfg["lora"]["b_std"]
    targets = sorted(p for p in base if p.rsplit("/", 1)[-1] in TARGETS)
    for i, path in enumerate(targets):
        lead, (d_in, d_out) = base[path].shape[:-2], base[path].shape[-2:]
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        adapters[f"{path}/lora_a"] = (jax.random.normal(ka, lead + (d_in, r))
                                      / math.sqrt(d_in))
        adapters[f"{path}/lora_b"] = jax.random.normal(
            kb, lead + (r, d_out)) * b_std
    return base, adapters


def yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim, theta, rs):
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies (dim // 2,)."""
    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extra, inter = 1.0 / pos, 1.0 / (rs["factor"] * pos)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low),
                   0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def _rope(x, inv, m, dt):
    """x (..., T, d): de-interleave (pairs 2i, 2i+1 -> i, i + d/2), then
    ``x cos + rotate_half(x) sin`` with cos/sin of ``cat(freqs, freqs)``
    scaled by ``m``."""
    t, d = x.shape[-2], x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (d,))
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = (jnp.cos(emb) * m).astype(dt), (jnp.sin(emb) * m).astype(dt)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rot * sin


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _swiglu(x, wg, wu, wd, prec):
    g = jnp.einsum("...d,df->...f", x, wg, precision=prec)
    u = jnp.einsum("...d,df->...f", x, wu, precision=prec)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, wd, precision=prec)


def _layer(tree, i, first_k_dense):
    """Layer ``i``'s entries of a flat dict, their paths from ``b/`` on."""
    if i < first_k_dense:
        pre = f"layers/{i:02d}/b/"
        return {k[len(pre):]: v for k, v in tree.items() if k.startswith(pre)}
    pre = "blocks/b/"
    return {k[len(pre):]: v[i - first_k_dense] for k, v in tree.items()
            if k.startswith(pre)}


def _proj(x, w, ad, name, s, prec):
    y = jnp.einsum("...d,dh->...h", x, w[name], precision=prec)
    a, b = ad[f"{name}/lora_a"], ad[f"{name}/lora_b"]
    xa = jnp.einsum("...d,dr->...r", x, a, precision=prec)
    return y + s * jnp.einsum("...r,rh->...h", xa, b, precision=prec)


def _mla(x, w, ad, m, dt, prec):
    b, t, _ = x.shape
    h, dn, dr, dv, r = (m["heads"], m["qk_nope"], m["qk_rope"], m["v_head"],
                        m["kv_lora_rank"])
    s = m["lora_alpha"] / m["lora_rank"]
    rs = m["rope_scaling"]
    inv = yarn_inv_freq(dr, m["rope_theta"], rs)
    rot_m = (yarn_get_mscale(rs["factor"], rs["mscale"])
             / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]))
    q = _proj(x, w, ad, "attn/wq", s, prec).reshape(b, t, h, dn + dr)
    q = jnp.swapaxes(q, 1, 2)                               # (B, H, T, 192)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], inv, rot_m, dt)
    ckv = _proj(x, w, ad, "attn/wkv_down", s, prec)
    c = _rms(ckv[..., :r], w["attn/kv_norm"], m["norm_eps"])
    k_pe = _rope(ckv[..., r:][:, None], inv, rot_m, dt)     # (B, 1, T, 64)
    kv = _proj(c, w, ad, "attn/wkv_up", s, prec).reshape(b, t, h, dn + dv)
    kv = jnp.swapaxes(kv, 1, 2)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5 * yarn_get_mscale(rs["factor"],
                                                 rs["mscale_all_dim"]) ** 2
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope, precision=prec)
              + jnp.einsum("bhqd,bxkd->bhqk", q_pe, k_pe, precision=prec))
    scores = scores * jnp.asarray(scale, dt)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, jnp.asarray(-1e30, dt))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=prec)
    out = jnp.swapaxes(out, 1, 2).reshape(b, t, h * dv)
    return _proj(out, w, ad, "attn/wo", s, prec)


def _moe(x, w, m, prec):
    scores = jnp.einsum("btd,de->bte", x, w["moe/router"], precision=prec)
    probs = jax.nn.softmax(scores, axis=-1)
    topw, topi = jax.lax.top_k(probs, m["experts_per_token"])
    if m["norm_topk_prob"]:
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    else:
        topw = topw * m["routed_scaling_factor"]
    y = jnp.zeros_like(x)
    for e in range(w["moe/w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(topi == m["held_first"] + e, topw, 0), axis=-1)
        y = y + gate[..., None] * _swiglu(x, w["moe/w_gate"][e],
                                          w["moe/w_up"][e], w["moe/w_down"][e],
                                          prec)
    return y + _swiglu(x, w["moe/ws_gate"], w["moe/ws_up"], w["moe/ws_down"],
                       prec)


def logits(adapters, frozen, inputs, model, dt=jnp.float32):
    """Logits (B, T, V) over the vocabulary slice for the ids ``inputs``
    (B, T), the base ``frozen`` and the ``adapters`` computed in ``dt``
    (float32: every matmul at ``highest``); ``model`` as ``model_of``
    gives it."""
    prec = "highest" if dt == jnp.float32 else None
    cast = functools.partial(jax.tree_util.tree_map, lambda a: a.astype(dt))
    frozen, adapters = cast(frozen), cast(adapters)
    x = frozen["embed"][inputs]
    eps, k0 = model["norm_eps"], model["first_k_dense"]
    for i in range(model["layers"]):
        w, ad = _layer(frozen, i, k0), _layer(adapters, i, k0)
        x = x + _mla(_rms(x, w["norm1"], eps), w, ad, model, dt, prec)
        hn = _rms(x, w["norm2"], eps)
        if i < k0:
            x = x + _swiglu(hn, w["mlp/w_gate"], w["mlp/w_up"], w["mlp/w_down"],
                            prec)
        else:
            x = x + _moe(hn, w, model, prec)
    x = _rms(x, frozen["final_norm"], eps)
    return jnp.einsum("btd,dv->btv", x, frozen["unembed"], precision=prec)


def loss(adapters, frozen, tokens, model, dt=jnp.float32):
    """Mean next-token cross-entropy of ``tokens`` (B, T + 1) (``logits``)."""
    lg = logits(adapters, frozen, tokens[:, :-1], model, dt)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


# -------------------------------------------------------- the GLR-CUCB part
def _ucb(mu, cnt, tau, t, gamma):
    since = jnp.maximum((t - tau).astype(mu.dtype), 2)
    bonus = jnp.sqrt(3 * jnp.log(since) / (2 * jnp.maximum(cnt, 1)))
    return jnp.where(cnt > 0, mu + gamma * bonus, jnp.asarray(1e9, mu.dtype))


def _kl(p, q):
    eps = 1e-6
    p = jnp.clip(p, eps, 1 - eps)
    q = jnp.clip(q, eps, 1 - eps)
    return p * jnp.log(p / q) + (1 - p) * jnp.log((1 - p) / (1 - q))


def _glr_fire(ring, cnt, sched, h, delta, min_samples, dt):
    n = jnp.minimum(cnt, h).astype(jnp.int32)
    oldest = jnp.mod(cnt.astype(jnp.int32) - n, h)
    s = jnp.arange(1, h + 1)
    x = jnp.take_along_axis(ring, jnp.mod(oldest[:, None] + s[None, :] - 1, h),
                            axis=1)
    x = jnp.where(s[None, :] <= n[:, None], x, 0)
    prefix = jnp.cumsum(x, axis=1)
    total = prefix[:, -1:]
    n_f, s_f = n[:, None].astype(dt), s[None, :].astype(dt)
    mu = total / jnp.maximum(n_f, 1)
    stat = (s_f * _kl(prefix / s_f, mu)
            + (n_f - s_f) * _kl((total - prefix) / jnp.maximum(n_f - s_f, 1), mu))
    stat = jnp.max(jnp.where(s[None, :] <= n[:, None] - 1, stat, -jnp.inf), axis=1)
    nn = jnp.maximum(n, 1).astype(dt)
    thresh = (1 + 1 / nn) * jnp.log(3 * nn * jnp.sqrt(nn) / delta)
    return jnp.any(sched & (stat >= thresh) & (n >= min_samples))


# ------------------------------------------------------------ the FL round
def _row_where(mask, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(mask.reshape((-1,) + (1,) * (x.ndim - 1)), x, y),
        a, b)


def _dot_rows(a, b):
    return sum(jnp.sum((x * y).reshape(x.shape[0], -1), axis=1)
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _round(st, key, frozen, data, cfg, dt):
    """One round; returns the new state and the round's mean local loss."""
    rnd, sch, mdl = cfg["round"], cfg["scheduler"], model_of(cfg)
    m, nch, n = rnd["n_sched"], sch["n_channels"], cfg["population"]["n_clients"]
    lr = rnd["client_lr"]
    beta = dt(sch["matcher_beta"])
    client_x, proxy_x, means, breaks = data
    k_env, k_sel = jax.random.split(key)
    t = st["t"]

    def prio(contrib, aoi, vmax, amax):
        v = jnp.sum((aoi - jnp.mean(aoi)) ** 2)
        vmax2, amax2 = jnp.maximum(vmax, v), jnp.maximum(amax, jnp.max(aoi))
        beta_t = beta * jnp.where(vmax2 > 0, v / vmax2, 0)
        c_t = contrib / jnp.maximum(jnp.max(contrib), 1e-12)
        a_t = jnp.where(amax2 > 0, aoi / amax2, 0)
        return (1 - beta_t) * c_t + beta_t * a_t, vmax2, amax2

    # 1. selection
    lam, _, _ = prio(st["contrib"], st["aoi"], st["vmax"], st["amax"])
    _, sel = jax.lax.top_k(jnp.where(st["avail"] > 0.5, lam, -jnp.inf), m)
    sel = jnp.sort(sel)
    avail_sel = st["avail"][sel]
    prev = st["slot_of"][sel]
    carry = prev >= 0
    src = jnp.clip(prev, 0, m - 1)
    zero = jax.tree_util.tree_map(jnp.zeros_like, st["buf"])
    carried = _row_where(carry, jax.tree_util.tree_map(lambda x: x[src], st["buf"]), zero)
    cb_g = _row_where(carry, jax.tree_util.tree_map(lambda x: x[src], st["cb_g"]), zero)
    cb_p = _row_where(carry, jax.tree_util.tree_map(lambda x: x[src], st["cb_p"]), zero)
    cb_f = jnp.where(carry, st["cb_f"][src], 0)

    # 2. local training, one client at a time
    k_data = jax.random.fold_in(key, DATA_TAG)
    n_ex = client_x.shape[1]
    idx = jax.vmap(lambda c: jax.random.randint(
        jax.random.fold_in(k_data, c), (rnd["local_steps"], rnd["batch_size"]),
        0, n_ex))(sel)
    bx = jax.vmap(lambda c, i: client_x[c][i])(sel, idx)
    grad = jax.value_and_grad(
        lambda a, x: loss(a, frozen, x, mdl, dt))

    def client(x):
        def step(w, b):
            val, g = grad(w, b)
            return jax.tree_util.tree_map(lambda p, gi: p - dt(lr) * gi, w, g), val
        w_end, vals = jax.lax.scan(step, st["w"], x)
        return (jax.tree_util.tree_map(lambda a, b: (a - b) / dt(lr),
                                       st["w"], w_end), vals[-1])

    fresh, losses = jax.lax.map(client, bx)
    active = jnp.where(avail_sel > 0.5, st["last_success"][sel], 0)
    buf = _row_where(active > 0.5, fresh, carried)
    has_upd = jnp.maximum(st["has_update"][sel], active)
    stale_sel = jnp.where(active > 0.5, 1, st["staleness"][sel] + 1)

    # 3. schedule, match, transmit
    ucb = _ucb(st["mu"], st["cnt"], st["tau"], t, dt(sch["gamma"]))
    jitter = jnp.where(st["cnt"] > 0, 0,
                       (jax.random.uniform(k_sel, (nch,)) * 1e6).astype(dt))
    top = jnp.argsort(-(ucb + jitter), stable=True)[:m]
    channels = top[(jnp.arange(m) + t) % m]
    contrib_sel, aoi_sel = st["contrib"][sel], st["aoi"][sel]
    lam_sel, vmax2, amax2 = prio(contrib_sel, aoi_sel, st["vmax"], st["amax"])
    assign = jnp.zeros((m,), jnp.int32).at[jnp.argsort(-lam_sel, stable=True)].set(
        channels[jnp.argsort(-ucb[channels], stable=True)])
    seg = jnp.searchsorted(breaks, t, side="right")
    up = jax.random.bernoulli(k_env, means[seg]).astype(dt)
    success = (up[assign] > 0.5).astype(dt) * has_upd * (avail_sel > 0.5)

    # 4. aggregate (the zeta-weighted mean) and step
    finite = jnp.stack([jnp.all(jnp.isfinite(x.reshape(m, -1)), axis=1)
                        for x in jax.tree_util.tree_leaves(buf)]).all(axis=0)
    row_ok = finite.astype(dt)
    mask = success * row_ok
    n_succ = jnp.sum(mask)
    zeta_sel = st["zeta"][sel]
    agg_buf = _row_where(mask > 0.5, buf, zero)
    scale = mask * zeta_sel * (m / jnp.maximum(n_succ, 1))
    agg = jax.tree_util.tree_map(
        lambda x: jnp.tensordot(scale, x, axes=1, precision="highest"), agg_buf)
    w = jax.tree_util.tree_map(
        lambda p, a: jnp.where(n_succ > 0, p - dt(rnd["server_lr"] / m) * a, p),
        st["w"], agg)
    has_upd = has_upd * row_ok
    last_sel = jnp.maximum(mask, 1 - row_ok)

    # GLR-CUCB learns from the delivered channel states
    sched = jnp.zeros((nch,), bool).at[assign].set(True)
    r = jnp.zeros((nch,), dt).at[assign].set(up[assign])
    cnt2 = jnp.where(sched, st["cnt"] + 1, st["cnt"])
    mu2 = jnp.where(sched, (st["mu"] * st["cnt"] + r) / (st["cnt"] + 1), st["mu"])
    slot = jnp.mod(st["cnt"].astype(jnp.int32), sch["history"])
    rows = jnp.arange(nch)
    ring = st["ring"].at[rows, slot].set(jnp.where(sched, r, st["ring"][rows, slot]))
    fire = ((t % sch["detector_stride"]) == 0) & _glr_fire(
        ring, cnt2, sched, sch["history"], sch["delta"], sch["min_samples"], dt)

    # 5. contribution (leave-one-out, one model at a time) and weights
    s = mask > 0.5
    cb_g = _row_where(s, agg_buf, cb_g)
    cb_p = _row_where(s, jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (m,) + p.shape), w), cb_p)
    cb_f = jnp.maximum(cb_f, s.astype(dt))
    wgt = zeta_sel * cb_f
    wsum = jnp.maximum(jnp.sum(wgt), 1e-12)
    denom = jnp.maximum(wsum - wgt, 1e-12)

    def loo(x):
        wx = wgt.reshape((-1,) + (1,) * (x.ndim - 1))
        return (jnp.sum(wx * x, axis=0) - wx * x) / denom.reshape(wx.shape)

    g_loo = jax.tree_util.tree_map(loo, cb_g)
    p_loo = jax.tree_util.tree_map(loo, cb_p)
    cos = _dot_rows(cb_g, g_loo) / jnp.maximum(
        jnp.sqrt(_dot_rows(cb_g, cb_g)) * jnp.sqrt(_dot_rows(g_loo, g_loo)), 1e-12)
    chunk = rnd["proxy_chunk"]
    chunks = proxy_x.reshape(-1, chunk, proxy_x.shape[-1])

    def proxy(p):
        return jnp.mean(jax.lax.map(lambda x: loss(p, frozen, x, mdl, dt),
                                    chunks))

    err = jax.lax.map(proxy, p_loo)
    c_rows = (1 - cos) * err.astype(dt)
    seen = cb_f > 0.5
    fill = jnp.where(jnp.any(seen), jnp.sum(jnp.where(seen, c_rows, 0))
                     / jnp.maximum(jnp.sum(seen), 1), 1)
    c_rows = jnp.where(seen, c_rows, fill)
    z_rows = jnp.maximum(c_rows, 1e-12) / jnp.sum(jnp.maximum(c_rows, 1e-12))

    # AoI, staleness, slots, availability
    agg_full = jnp.zeros((n,), bool).at[sel].set(s)
    act_full = jnp.zeros((n,), bool).at[sel].set(active > 0.5)
    staleness = jnp.where(act_full, 1, st["staleness"] + 1).at[sel].set(stale_sel)
    old = st["slot_clients"]
    slot_of = st["slot_of"].at[jnp.where(old >= 0, old, n)].set(-1, mode="drop")
    slot_of = slot_of.at[sel].set(jnp.arange(m, dtype=jnp.int32))
    evicted = (old >= 0) & (slot_of[jnp.clip(old, 0, n - 1)] < 0)
    ev = jnp.where(evicted, old, n)
    k0, k1 = jax.random.split(jax.random.fold_in(key, AVAIL_TAG))
    drop = jax.random.bernoulli(k0, cfg["availability"]["p_drop"], (n,))
    rejoin = jax.random.bernoulli(k1, cfg["availability"]["p_rejoin"], (n,))
    dropped = st["phase"] == DROPPED
    phase = jnp.where(dropped, jnp.where(rejoin, IDLE, DROPPED),
                      jnp.where(drop, DROPPED, st["phase"]))

    ok = jnp.isfinite(losses)
    local_loss = (jnp.sum(jnp.where(ok, losses, 0) * active)
                  / jnp.maximum(jnp.sum(active * ok), 1))
    new = dict(
        w=w, buf=buf, slot_clients=sel.astype(jnp.int32),
        cb_g=cb_g, cb_p=cb_p, cb_f=cb_f, slot_of=slot_of,
        has_update=st["has_update"].at[sel].set(has_upd).at[ev].set(0, mode="drop"),
        last_success=st["last_success"].at[sel].set(last_sel).at[ev].set(1, mode="drop"),
        aoi=jnp.where(agg_full, 1, st["aoi"] + 1), staleness=staleness,
        contrib=st["contrib"].at[sel].set(c_rows),
        zeta=st["zeta"].at[sel].set(z_rows),
        avail=(phase != DROPPED).astype(dt), phase=phase,
        mu=jnp.where(fire, 0, mu2), cnt=jnp.where(fire, 0, cnt2),
        tau=jnp.where(fire, t, st["tau"]), ring=ring,
        vmax=vmax2, amax=amax2, t=t + 1)
    return new, (local_loss, n_succ)


def init_state(adapters, cfg, dt):
    m, n = cfg["round"]["n_sched"], cfg["population"]["n_clients"]
    nch, h = cfg["scheduler"]["n_channels"], cfg["scheduler"]["history"]
    w = jax.tree_util.tree_map(lambda p: p.astype(dt), adapters)
    rows = jax.tree_util.tree_map(lambda p: jnp.zeros((m,) + p.shape, dt), w)
    return dict(
        w=w, buf=rows, slot_clients=jnp.full((m,), -1, jnp.int32),
        cb_g=rows, cb_p=rows, cb_f=jnp.zeros((m,), dt),
        slot_of=jnp.full((n,), -1, jnp.int32),
        has_update=jnp.zeros((n,), dt), last_success=jnp.ones((n,), dt),
        aoi=jnp.ones((n,), dt), staleness=jnp.ones((n,), dt),
        contrib=jnp.ones((n,), dt), zeta=jnp.full((n,), 1 / m, dt),
        avail=jnp.ones((n,), dt), phase=jnp.zeros((n,), jnp.int32),
        mu=jnp.zeros((nch,), dt), cnt=jnp.zeros((nch,), dt),
        tau=jnp.zeros((), jnp.int32), ring=jnp.zeros((nch, h), dt),
        vmax=jnp.zeros((), dt), amax=jnp.ones((), dt),
        t=jnp.zeros((), jnp.int32))


def replay(adapters, frozen, data, keys, cfg, dtype=jnp.float32,
           estimates=None):
    """Follow the trainer's rounds ``keys`` from ``adapters`` on the base
    ``frozen``, aggregating by the zeta-weighted mean.

    ``data`` is ``(client tokens (N, n, T + 1), proxy tokens (P, T + 1),
    channel means (S, N), breakpoints (S-1,))``.  ``estimates``, where
    given, holds for each round None or the contribution estimate the
    replayed program had after it, ``(contrib (N,), zeta (N,))``: the
    replay records its own estimate and goes on from the given one.  The
    next round's selection and channel matching rank clients by that
    estimate, and rounding in a sound program can swap two near-equal
    priorities, after which the two runs schedule different clients for
    good; so each round is followed from the same estimate, and the
    estimates themselves are compared.

    Returns each round's mean local loss, the adapters after the rounds,
    the last round's client updates as held in the slots (``buf``), all in
    float32, each round's count of delivered updates, the discrete state
    after the rounds (the last round's selected clients, which clients hold
    an update, each client's AoI), and each round's selected clients with
    this replay's own contribution estimate for them.
    """
    st = init_state(adapters, cfg, dtype)
    step = jax.jit(functools.partial(_round, cfg=cfg, dt=dtype))
    losses, delivered, own = [], [], []
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        for r, k in enumerate(keys):
            st, (l_, n_) = step(st, k, frozen, data)
            losses.append(float(l_))
            delivered.append(float(n_))
            sel = np.asarray(st["slot_clients"])
            own.append((sel, np.asarray(st["contrib"], np.float32)[sel]))
            if estimates is not None and estimates[r] is not None:
                contrib, zeta = estimates[r]
                st = dict(st, contrib=jnp.asarray(contrib, dtype),
                          zeta=jnp.asarray(zeta, dtype))
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda x: np.asarray(x, np.float32))
    events = {k: np.asarray(st[k]) for k in ("slot_clients", "has_update",
                                             "aoi")}
    return losses, f32(st["w"]), f32(st["buf"]), delivered, events, own
