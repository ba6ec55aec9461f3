"""Plain reference for the ``fedcnn-pop`` deployment: the FedAvg CNN and
the first rounds of population-scale asynchronous FL.

The model is the MNIST CNN of McMahan et al., "Communication-Efficient
Learning of Deep Networks from Decentralized Data" (AISTATS 2017): two
5x5 convolutions of 32 and 64 channels, each followed by 2x2 max pooling,
a 512-unit ReLU layer and a 10-way softmax, 1,663,370 parameters.  Images
are 28x28 uint8 rows of 784 bytes, scaled to [0, 1] inside the loss.  ``init`` and ``loss``
are what the benchmark hands the trainer as its model.

``replay`` follows the trainer's first rounds from the same seed, inputs
and settings, one plain step at a time: the round protocol of the paper
(arXiv:2503.01324, Alg. 1 with Sec. V) at population scale -

1. select the M available clients of highest priority
   ``(1 - beta_t) C_i / max C + beta_t a_i / a_max`` (Eq. 39-40, the
   running maxima not committed by the selection);
2. each selected client that is due to train runs E steps of SGD on B of
   its examples (drawn by the round's data key and its id) from the global
   model and keeps ``G = (w0 - wE) / lr`` (Eq. 5-6); others resend what
   they hold from their slot;
3. GLR-CUCB picks M channels, the matcher assigns them (Eq. 30, 36-40),
   the channels are drawn from the round's environment key, and a client
   whose channel is up and who holds an update is delivered;
4. the delivered updates are aggregated (the zeta-weighted mean of Eq. 7,
   or the coordinate-wise median) and the server steps by
   ``-server_lr / M`` times the aggregate;
5. the contribution buffer takes the delivered updates, and each slot's
   contribution ``(1 - cos(g_m, g_-m)) * L_proxy(w_-m)`` (Eq. 33-35,
   leave-one-out over the buffer with zeta weights) and its new weight
   ``zeta`` (Eq. 43) follow; AoI (Eq. 8), staleness, slot ownership and
   client availability (two-state Markov churn) advance.

It imports nothing of the program, and it keeps the model as a pytree
(the program flattens it).  ``dtype`` sets the precision of everything
it computes.  float32, with the chip's default matmul precision (one
bfloat16 pass, float32 accumulation), is the configuration's arithmetic,
the one the trainer runs; bfloat16 throughout is the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DATA_TAG = 0xDA7A      # the round key's data stream
AVAIL_TAG = 0xA7A1     # the round key's availability stream
IDLE, DROPPED = 0, 2


# --------------------------------------------------------------- the model
def init(key, model):
    """Seeded weights, uniform in +-1/sqrt(fan_in) (float32)."""
    k = model["kernel"]
    shapes = {
        "conv1": ((k, k, 1, model["conv1"]), k * k),
        "conv2": ((k, k, model["conv1"], model["conv2"]), k * k * model["conv1"]),
        "fc1": ((model["conv2"] * (model["image"] // 4) ** 2, model["fc"]),
                model["conv2"] * (model["image"] // 4) ** 2),
        "fc2": ((model["fc"], model["classes"]), model["fc"]),
    }
    params = {}
    for i, (name, (shape, fan_in)) in enumerate(sorted(shapes.items())):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        bound = 1.0 / np.sqrt(fan_in)
        params[name] = {
            "w": jax.random.uniform(kw, shape, jnp.float32, -bound, bound),
            "b": jax.random.uniform(kb, shape[-1:], jnp.float32, -bound, bound)}
    return params


def _pool(h):
    return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def loss(params, x, y, precision=None):
    """Mean softmax cross-entropy of the CNN on uint8 images ``x`` (B, 784),
    row-major 28x28 as MNIST stores them, with labels ``y`` (B,), computed
    in the dtype of ``params``."""
    dt = params["fc2"]["w"].dtype
    side = int(round(x.shape[-1] ** 0.5))
    h = (x.astype(jnp.float32) / 255.0).astype(dt).reshape(-1, side, side, 1)
    for name in ("conv1", "conv2"):
        h = jax.lax.conv_general_dilated(
            h, params[name]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        h = _pool(jax.nn.relu(h + params[name]["b"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.dot(h, params["fc1"]["w"], precision=precision)
                    + params["fc1"]["b"])
    logits = jnp.dot(h, params["fc2"]["w"], precision=precision) + params["fc2"]["b"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def forward_flops(model):
    """Multiply-adds x 2 of one image's forward pass."""
    k, s = model["kernel"], model["image"]
    c1, c2, fc, out = model["conv1"], model["conv2"], model["fc"], model["classes"]
    macs = (s * s * c1 * k * k
            + (s // 2) ** 2 * c2 * k * k * c1
            + (s // 4) ** 2 * c2 * fc
            + fc * out)
    return 2 * macs


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
def _tree_rows(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _row_where(mask, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(mask.reshape((-1,) + (1,) * (x.ndim - 1)), x, y),
        a, b)


def _dot_rows(a, b):
    return sum(jnp.sum((x * y).reshape(x.shape[0], -1), axis=1)
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _median_rows(x, mask, n_succ):
    """Per coordinate, the mean of the delivered values with the
    floor((n-1)/2) smallest and largest dropped; zeros if none."""
    k = jnp.maximum(jnp.floor((n_succ - 1) / 2), 0)
    big = jnp.asarray(jnp.inf, x.dtype)
    srt = jnp.sort(jnp.where(mask.reshape((-1,) + (1,) * (x.ndim - 1)) > 0.5,
                             x, big), axis=0)
    r = jnp.arange(x.shape[0]).reshape((-1,) + (1,) * (x.ndim - 1))
    keep = (r >= k) & (r < n_succ - k)
    return (jnp.sum(jnp.where(keep, srt, 0), axis=0)
            / jnp.maximum(n_succ - 2 * k, 1)).astype(x.dtype)


def _round(st, key, data, cfg, aggregator, dt, precision):
    """One round; returns the new state and the round's mean local loss."""
    rnd, sch, mdl = cfg["round"], cfg["scheduler"], cfg["model"]
    m, nch, n = rnd["n_sched"], sch["n_channels"], cfg["population"]["n_clients"]
    lr = rnd["client_lr"]
    beta = dt(sch["matcher_beta"])
    client_x, client_y, proxy_x, proxy_y, means, breaks = data
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

    # 2. local training
    k_data = jax.random.fold_in(key, DATA_TAG)
    n_ex = client_y.shape[1]
    idx = jax.vmap(lambda c: jax.random.randint(
        jax.random.fold_in(k_data, c), (rnd["local_steps"], rnd["batch_size"]),
        0, n_ex))(sel)
    bx = jax.vmap(lambda c, i: client_x[c][i])(sel, idx)
    by = jax.vmap(lambda c, i: client_y[c][i])(sel, idx)
    grad = jax.value_and_grad(functools.partial(loss, precision=precision))

    def client(x, y):
        def step(w, b):
            val, g = grad(w, *b)
            return jax.tree_util.tree_map(lambda p, gi: p - dt(lr) * gi, w, g), val
        w_end, vals = jax.lax.scan(step, st["w"], (x, y))
        return (jax.tree_util.tree_map(lambda a, b: (a - b) / dt(lr),
                                       st["w"], w_end), vals[-1])

    fresh, losses = jax.vmap(client)(bx, by)
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

    # 4. aggregate and step
    finite = jnp.stack([jnp.all(jnp.isfinite(x.reshape(m, -1)), axis=1)
                        for x in jax.tree_util.tree_leaves(buf)]).all(axis=0)
    row_ok = finite.astype(dt)
    mask = success * row_ok
    n_succ = jnp.sum(mask)
    zeta_sel = st["zeta"][sel]
    agg_buf = _row_where(mask > 0.5, buf, zero)
    if aggregator == "mean":
        scale = mask * zeta_sel * (m / jnp.maximum(n_succ, 1))
        agg = jax.tree_util.tree_map(
            lambda x: jnp.tensordot(scale, x, axes=1, precision="highest"), agg_buf)
    else:
        agg = jax.tree_util.tree_map(lambda x: _median_rows(x, mask, n_succ), agg_buf)
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

    # 5. contribution and weights
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
    err = jax.vmap(lambda p: loss(p, proxy_x, proxy_y, precision))(p_loo)
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
    act = active
    local_loss = (jnp.sum(jnp.where(ok, losses, 0) * act)
                  / jnp.maximum(jnp.sum(act * ok), 1))
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


def init_state(params, cfg, dt):
    m, n = cfg["round"]["n_sched"], cfg["population"]["n_clients"]
    nch, h = cfg["scheduler"]["n_channels"], cfg["scheduler"]["history"]
    w = jax.tree_util.tree_map(lambda p: p.astype(dt), params)
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


def replay(params, data, keys, cfg, aggregator, dtype=jnp.float32):
    """Follow the trainer's rounds ``keys`` from ``params``, aggregating
    by ``aggregator`` (``mean`` or ``coordinate_median``).

    ``data`` is ``(client_x, client_y, proxy_x, proxy_y, channel means
    (S, N), breakpoints (S-1,))``.  Returns each round's mean local loss,
    the params after the rounds, the last round's client updates as held
    in the slots (``buf``), all in float32, each round's count of
    delivered updates, and the discrete state after the rounds (the last
    round's selected clients, which clients hold an update, each client's
    AoI), for a look at where the program and the reference part.
    """
    st = init_state(params, cfg, dtype)
    step = jax.jit(functools.partial(_round, cfg=cfg, aggregator=aggregator,
                                     dt=dtype, precision=None))
    losses, delivered = [], []
    for k in keys:
        st, (l_, n_) = step(st, k, data)
        losses.append(float(l_))
        delivered.append(float(n_))
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda x: np.asarray(x, np.float32))
    events = {k: np.asarray(st[k]) for k in ("slot_clients", "has_update",
                                             "aoi")}
    return losses, f32(st["w"]), f32(st["buf"]), delivered, events
