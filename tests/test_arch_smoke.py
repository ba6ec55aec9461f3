"""Per-architecture smoke tests (deliverable f).

For each of the 11 assigned architectures: instantiate the REDUCED
variant of the same family (<=2-3 layers, d_model<=512, <=4 experts),
run one forward + one train step on CPU, assert output shapes and no
NaNs; decoders additionally run one serve step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config, list_archs
from repro.models import build_model
from repro.optim import adamw
from repro.optim.optimizers import apply_updates

KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def _batch_for(cfg):
    if cfg.arch_type == "audio":
        return {
            "frames": jax.random.normal(KEY, (B, S, cfg.d_model), jnp.bfloat16),
            "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
            "mask": jax.random.bernoulli(KEY, 0.2, (B, S)),
        }
    batch = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if cfg.arch_type == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            KEY, (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    return batch


def test_all_archs_have_smoke_configs():
    assert len(list_archs()) == 11


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_config_is_reduced_same_family(arch):
    full, smoke = get_config(arch), get_smoke_config(arch)
    assert smoke.arch_type == full.arch_type
    assert smoke.n_layers <= 3
    assert smoke.d_model <= 512
    assert smoke.n_experts <= 4
    assert smoke.attention == full.attention
    assert bool(smoke.layer_pattern) == bool(full.layer_pattern)


@pytest.mark.parametrize("arch", list_archs())
def test_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg, remat="none")
    params, specs = model.init(KEY)
    assert set(specs) == set(params)
    batch = _batch_for(cfg)

    logits, aux = model.apply(params, batch)
    expect_s = S + (cfg.frontend_tokens if cfg.arch_type == "vlm" else 0)
    assert logits.shape == (B, expect_s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    opt = adamw(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(p, o):
        (loss, metrics), grads = jax.value_and_grad(
            lambda q: model.loss(q, batch), has_aux=True)(p)
        upd, o = opt.update(grads, o, p)
        return apply_updates(p, upd), o, loss

    p1, opt_state, loss1 = train_step(params, opt_state)
    assert bool(jnp.isfinite(loss1))
    # a second step from updated params keeps everything finite
    p2, _, loss2 = train_step(p1, opt_state)
    assert bool(jnp.isfinite(loss2))
    changed = any(
        not np.allclose(np.asarray(params[k], np.float32),
                        np.asarray(p1[k], np.float32))
        for k in params)
    assert changed


@pytest.mark.parametrize("arch", [a for a in list_archs()
                                  if not get_smoke_config(a).is_encoder])
def test_serve_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg, remat="none")
    params, _ = model.init(KEY)
    cache = model.init_cache(B, 64)
    tok = jnp.zeros((B,), jnp.int32)
    step = jax.jit(model.decode_step)
    for _ in range(3):
        logits, cache = step(params, cache, tok)
        assert logits.shape == (B, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all())
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    assert int(cache["pos"]) == 3


def test_encoder_has_no_decode():
    cfg = get_smoke_config("hubert-xlarge")
    model = build_model(cfg)
    with pytest.raises(ValueError):
        model.init_cache(B, 64)


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "mamba2-1.3b", "recurrentgemma-2b"])
def test_decode_matches_prefill_f32(arch):
    """Cache correctness: sequential decode reproduces teacher-forced logits."""
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    model = build_model(cfg, remat="none")
    params, _ = model.init(KEY)
    toks = jax.random.randint(KEY, (1, 12), 0, cfg.vocab_size)
    full, _ = model.apply(params, {"tokens": toks})
    cache = model.init_cache(1, 12, dtype=jnp.float32)
    step = jax.jit(model.decode_step)
    for t in range(12):
        lg, cache = step(params, cache, toks[:, t])
        np.testing.assert_allclose(
            lg, full[:, t].astype(jnp.float32), rtol=2e-3, atol=2e-3)


def test_rglru_block_diagonal_gates_decode_consistency():
    """The §Perf block-diagonal gate variant stays decode-consistent."""
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config("recurrentgemma-2b"),
                              dtype="float32", lru_gate_blocks=4)
    model = build_model(cfg, remat="none")
    params, _ = model.init(KEY)
    toks = jax.random.randint(KEY, (1, 10), 0, cfg.vocab_size)
    full, _ = model.apply(params, {"tokens": toks})
    cache = model.init_cache(1, 10, dtype=jnp.float32)
    step = jax.jit(model.decode_step)
    for t in range(10):
        lg, cache = step(params, cache, toks[:, t])
        np.testing.assert_allclose(
            lg, full[:, t].astype(jnp.float32), rtol=2e-3, atol=2e-3)
