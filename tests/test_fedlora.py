"""Federated LoRA on DeepSeek-V2-Lite's blocks at smoke widths (CPU): the
program against the plain reference of ``bench/configs`` (logits, loss,
LoRA gradients), the held-experts layer's share and dropless guarantee,
YaRN at the published dims, and the trainer's ``frozen`` operand."""
import dataclasses
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import build_model, lora, moe
from repro.models.attention import mla_softmax_scale
from repro.models.layers import yarn_freqs, yarn_get_mscale

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey(0)


def _reference():
    path = os.path.join(ROOT, "bench", "configs", "dsv2lite-fedlora_reference.py")
    spec = importlib.util.spec_from_file_location("dsv2lite_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _config(**over):
    base = dict(dtype="float32", lora_rank=4, lora_alpha=8.0, n_layers=2,
                router_aux_weight=0.0, held_first=1, held_count=2)
    base.update(over)
    return dataclasses.replace(get_smoke_config("deepseek-v2-lite"), **base)


def _ref_model(mc):
    """The reference's model settings for a ``ModelConfig``."""
    rs = mc.rope_scaling
    return {"layers": mc.n_layers, "heads": mc.n_heads, "qk_nope": mc.qk_nope_dim,
            "qk_rope": mc.qk_rope_dim, "v_head": mc.v_head_dim,
            "kv_lora_rank": mc.kv_lora_rank, "rope_theta": mc.rope_theta,
            "rope_scaling": dataclasses.asdict(rs), "norm_eps": mc.norm_eps,
            "first_k_dense": mc.first_k_dense,
            "experts_per_token": mc.experts_per_token,
            "norm_topk_prob": mc.norm_topk_prob,
            "routed_scaling_factor": mc.routed_scaling_factor,
            "held_first": mc.held_first, "lora_rank": mc.lora_rank,
            "lora_alpha": mc.lora_alpha}


def _setup(mc, t=12):
    model = build_model(mc, remat="none")
    frozen, _ = model.init(KEY)
    adapters = lora.init_adapters(frozen, mc, jax.random.fold_in(KEY, 1),
                                  b_std=0.05)
    tokens = jax.random.randint(jax.random.fold_in(KEY, 2), (2, t + 1), 0,
                                mc.vocab_size)
    return model, frozen, adapters, tokens


def test_program_matches_reference_logits_loss_and_lora_grads():
    mc = _config()
    model, frozen, adapters, tokens = _setup(mc)
    logits, _ = jax.jit(model.apply)({**frozen, **adapters},
                                     {"tokens": tokens[:, :-1]})
    ref_logits = jax.jit(lambda a: REF.logits(
        a, frozen, tokens[:, :-1], _ref_model(mc)))(adapters)
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-4, atol=2e-4)

    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda a: model.loss(frozen, {"tokens": tokens}, adapters=a),
        has_aux=True))(adapters)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda a: REF.loss(a, frozen, tokens, _ref_model(mc))))(adapters)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    assert set(grads) == set(ref_grads)
    for k in grads:
        scale = float(jnp.max(jnp.abs(ref_grads[k])))
        np.testing.assert_allclose(grads[k], ref_grads[k], rtol=0,
                                   atol=1e-4 * max(scale, 1e-6), err_msg=k)


def _moe_params(mc, key):
    from repro.models.layers import ParamBuilder
    pb = ParamBuilder(key, dtype=jnp.float32)
    moe.add_moe_params(pb, "m", mc)
    return pb.params


def test_disjoint_shares_add_up_to_the_uncut_layer():
    """Two devices holding experts 0-1 and 2-3 of 4: their routed parts,
    with the shared experts (which both hold) counted once, are the uncut
    reference layer."""
    full = _config(held_first=0, held_count=0)
    p = _moe_params(full, jax.random.fold_in(KEY, 3))
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 9, full.d_model))
    outs = []
    for first in (0, 2):
        mc = dataclasses.replace(full, held_first=first, held_count=2)
        share = dict(p)
        for w in ("w_gate", "w_up", "w_down"):
            share[f"m/{w}"] = p[f"m/{w}"][first:first + 2]
        out, stats = moe.held_moe_ffn(share, "m", x, mc)
        assert int(stats["dropped"]) == 0
        outs.append((out, stats))
    shared = REF._swiglu(x, p["m/ws_gate"], p["m/ws_up"], p["m/ws_down"], "highest")
    both = outs[0][0] + outs[1][0] - shared
    ref = REF._moe(x, {f"moe/{k[2:]}": v for k, v in p.items()},
                   dict(_ref_model(full), held_first=0), "highest")
    np.testing.assert_allclose(both, ref, rtol=1e-4, atol=1e-5)
    routed = [np.asarray(s["routed"]) for _, s in outs]
    assert sum(int(r.sum()) for r in routed) == 2 * 9 * full.experts_per_token
    assert int(outs[0][1]["absent"]) == int(routed[1].sum())


def test_dropless_when_every_token_picks_one_held_expert():
    mc = _config(held_first=0, held_count=2)
    p = _moe_params(mc, jax.random.fold_in(KEY, 5))
    p["m/router"] = p["m/router"].at[:, 0].set(50.0)       # expert 0 first
    x = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 6), (2, 16, mc.d_model)))
    out, stats = moe.held_moe_ffn(p, "m", x, mc)
    assert int(stats["dropped"]) == 0
    assert int(stats["routed"][0]) == 2 * 16
    ref = REF._moe(x, {f"moe/{k[2:]}": v for k, v in p.items()},
                   _ref_model(mc), "highest")
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_held_experts_batch_by_folding_under_vmap_and_grad():
    mc = _config(held_first=1, held_count=2)
    p = _moe_params(mc, jax.random.fold_in(KEY, 7))
    x = jax.random.normal(jax.random.fold_in(KEY, 8), (3, 2, 8, mc.d_model))

    def f(xx):
        out, _ = moe.held_moe_ffn(p, "m", xx, mc)
        return jnp.sum(jnp.sin(out))

    batched = jax.jit(jax.vmap(jax.value_and_grad(f)))(x)
    for i in range(3):
        one = jax.value_and_grad(f)(x[i])
        np.testing.assert_allclose(batched[0][i], one[0], rtol=1e-5)
        np.testing.assert_allclose(batched[1][i], one[1], rtol=1e-4, atol=1e-6)


def test_rows_past_the_held_groups_never_reach_the_result(monkeypatch):
    """``ragged_dot`` leaves the rows past its groups unwritten on a TPU:
    filled with NaN here, in the forward pass and in its transposes,
    neither the output nor the gradients see them."""
    mc = _config(held_first=1, held_count=2)
    p = _moe_params(mc, jax.random.fold_in(KEY, 9))
    x = jax.random.normal(jax.random.fold_in(KEY, 10), (2, 8, mc.d_model))

    def f(xx):
        out, _ = moe.held_moe_ffn(p, "m", xx, mc)
        return jnp.sum(jnp.sin(out)), out

    (val, out), grad = jax.value_and_grad(f, has_aux=True)(x)
    ragged = jax.lax.ragged_dot

    def fill(y, sizes):
        rows = jnp.arange(y.shape[0])[:, None] < jnp.sum(sizes)
        return jnp.where(rows, y, jnp.nan)

    def unwritten(lhs, rhs, sizes, **kw):
        return fill(ragged(lhs, rhs, sizes, **kw), sizes)

    def unwritten_t(cot, w, sizes, d_in):
        like = jax.ShapeDtypeStruct((cot.shape[0], d_in), cot.dtype)
        return fill(jax.linear_transpose(
            lambda a: ragged(a, w, sizes), like)(cot)[0], sizes)

    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    monkeypatch.setattr(moe, "_ragged_t", unwritten_t)
    (val2, out2), grad2 = jax.value_and_grad(f, has_aux=True)(x)
    np.testing.assert_array_equal(out2, out)
    np.testing.assert_array_equal(grad2, grad)


def test_held_layer_gradients_match_the_reference_layer():
    """The held layer's VJP (rows kept from the forward, no recompute)
    against autodiff of the reference's per-expert loop."""
    mc = _config(held_first=1, held_count=2)
    p = _moe_params(mc, jax.random.fold_in(KEY, 11))
    x = jax.random.normal(jax.random.fold_in(KEY, 12), (2, 8, mc.d_model))
    wide = {f"moe/{k[2:]}": v for k, v in p.items()}

    def prog(xx):
        return jnp.sum(jnp.sin(moe.held_moe_ffn(p, "m", xx, mc)[0]))

    def ref(xx):
        return jnp.sum(jnp.sin(REF._moe(xx, wide, _ref_model(mc), "highest")))

    g, r = jax.jit(jax.grad(prog))(x), jax.jit(jax.grad(ref))(x)
    np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(r))))


def _ragged_dots(jaxpr, ranks):
    """The output rank of every ``ragged_dot`` in ``jaxpr`` and its
    sub-jaxprs: 2 for rows (forward, input transposes), 3 for weights."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("ragged_dot"):
            ranks.append(eqn.outvars[0].aval.ndim)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _ragged_dots(sub, ranks)
    return ranks


def test_held_layer_full_parameter_gradients_match_the_reference():
    """Every parameter of the held layer takes its gradient (full-parameter
    training): alone, vmapped over members with their own weights, and
    per example with shared weights; a frozen base runs no weight
    transposes."""
    mc = _config(held_first=1, held_count=2)
    p = _moe_params(mc, jax.random.fold_in(KEY, 13))
    x = jax.random.normal(jax.random.fold_in(KEY, 14), (2, 8, mc.d_model))

    def prog(pp, xx):
        return jnp.sum(jnp.sin(moe.held_moe_ffn(pp, "m", xx, mc)[0]))

    def ref(pp, xx):
        wide = {f"moe/{k[2:]}": v for k, v in pp.items()}
        return jnp.sum(jnp.sin(REF._moe(xx, wide, _ref_model(mc), "highest")))

    def close(got, want):
        for k in want:
            np.testing.assert_allclose(
                got[k], want[k], rtol=1e-4,
                atol=1e-5 * float(jnp.max(jnp.abs(want[k]))), err_msg=k)

    both = (0, 1)
    g, r = jax.jit(jax.grad(prog, both))(p, x), jax.jit(jax.grad(ref, both))(p, x)
    close(g[0], r[0])
    close({"x": g[1]}, {"x": r[1]})
    assert all(float(jnp.max(jnp.abs(g[0][f"m/{w}"]))) > 0
               for w in ("w_gate", "w_up", "w_down"))

    members = jax.tree_util.tree_map(
        lambda a: jnp.stack([a, 1.1 * a, 0.9 * a]), p)
    xs = jnp.stack([x, 0.5 * x, 2.0 * x])
    own = jax.jit(jax.vmap(jax.grad(prog, both)))(members, xs)
    shared = jax.jit(jax.vmap(jax.grad(prog), in_axes=(None, 0)))(p, xs)
    for i in range(3):
        pi = jax.tree_util.tree_map(lambda a: a[i], members)
        want = jax.grad(ref, both)(pi, xs[i])
        close(jax.tree_util.tree_map(lambda a: a[i], own[0]), want[0])
        close({"x": own[1][i]}, {"x": want[1]})
        close(jax.tree_util.tree_map(lambda a: a[i], shared),
              jax.grad(ref)(p, xs[i]))

    frozen = _ragged_dots(jax.make_jaxpr(jax.vmap(
        jax.grad(prog, 1), in_axes=(None, 0)))(p, xs).jaxpr, [])
    assert frozen == [2] * 6
    assert sorted(_ragged_dots(jax.make_jaxpr(jax.grad(prog, both))(
        p, x).jaxpr, [])).count(3) == 3


def test_yarn_at_the_published_dims():
    cfg = get_config("deepseek-v2-lite")
    rs = cfg.rope_scaling
    inv = yarn_freqs(cfg.qk_rope_dim, cfg.rope_theta, rs)
    ref = REF.yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta, dataclasses.asdict(rs))
    np.testing.assert_allclose(inv, ref, rtol=1e-6)
    base = 1.0 / cfg.rope_theta ** (np.arange(0, 64, 2) / 64)
    # correction dims 10 and 23 at 4096 positions: below 10 the published
    # frequencies, from 23 on divided by the factor 40
    np.testing.assert_allclose(inv[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    assert np.all((inv[10:23] < base[10:23]) & (inv[10:23] > base[10:23] / 40))
    m = yarn_get_mscale(40, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1) == pytest.approx(1.2608, abs=1e-4)
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)


def test_the_published_configs():
    lite, big = get_config("deepseek-v2-lite"), get_config("deepseek-v2-236b")
    assert (lite.d_model, lite.n_layers, lite.n_experts, lite.d_expert,
            lite.d_ff, lite.vocab_size, lite.q_lora_rank) == (
        2048, 27, 64, 1408, 10944, 102400, 0)
    assert not lite.norm_topk_prob and lite.capacity_factor == 0
    assert big.d_ff == 12288 and big.d_expert == 1536
    assert big.routed_scaling_factor == 16 and not big.norm_topk_prob
    assert big.rope_scaling == lite.rope_scaling


def _trainer(mc, frozen_like, proxy, adapters):
    from repro.core.bandits import GLRCUCB
    from repro.core.channels import segment_env
    from repro.fl.sparse import SparseAsyncFLTrainer, SparseFLConfig
    model = build_model(mc, remat="full")
    loss_fn, proxy_fn = lora.fl_loss_fns(model, proxy, adapters, 1)
    return SparseAsyncFLTrainer(
        cfg=SparseFLConfig(n_clients=8, n_sched=2, n_channels=3, batch_size=2,
                           local_epochs=2),
        scheduler=GLRCUCB(3, 2, history=8), env=segment_env(
            jnp.full((1, 3), 0.7)),
        loss_fn=loss_fn, proxy_loss_fn=proxy_fn)


def test_run_takes_the_frozen_weights_as_entry_parameters():
    """The lowered ``run`` has one entry parameter per frozen leaf and no
    constant of a frozen leaf's size: the base is not baked in."""
    from repro.fl.sparse import SparseAsyncFLTrainer
    mc = _config(dtype="bfloat16")
    model, frozen, adapters, tokens = _setup(mc)
    proxy = tokens
    trainer = _trainer(mc, frozen, proxy, adapters)
    state = trainer.init(adapters, KEY)
    cx = jax.random.randint(KEY, (8, 4, 13), 0, mc.vocab_size)
    cy = jnp.zeros((8, 4), jnp.int32)
    keys = jax.random.split(KEY, 2)
    lowered = SparseAsyncFLTrainer._run_plain.lower(
        trainer, state, cx, cy, keys, trainer.env, ("jnp", "jnp"), frozen)
    text = lowered.as_text()
    main = re.search(r"func\.func public @main\((.*?)\) ->", text, re.S).group(1)
    bf16_args = sorted(re.findall(r"%arg\d+: tensor<([\dx]+)xbf16>", main))
    assert bf16_args == sorted("x".join(map(str, w.shape))
                               for w in frozen.values())
    for w in frozen.values():
        shape = "x".join(map(str, w.shape))
        assert not re.search(rf"constant.*dense<.*tensor<{shape}xbf16>", text)
    # the round's counters come back per round, on the device
    _, metrics = trainer.run(state, cx, cy, keys, frozen=frozen)
    assert metrics["moe_routed"].shape == (2, mc.n_held_experts)
    assert int(jnp.sum(metrics["moe_dropped"])) == 0
