"""End-to-end behaviour tests for the paper's system.

The full pipeline at miniature scale: non-stationary channels -> MAB
scheduling -> adaptive matching -> async FL aggregation -> a trained
model that serves tokens.  Also covers the dry-run spec machinery in its
metadata-only form (real 512-device compiles run via
``python -m repro.launch.dryrun``; artifacts in experiments/dryrun/).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config, list_archs
from repro.core.bandits import GLRCUCB, MExp3, RandomScheduler
from repro.core.channels import random_adversarial_env, random_piecewise_env
from repro.core.regret import simulate_aoi_regret
from repro.models import build_model

KEY = jax.random.PRNGKey(0)


def test_paper_fig2a_ordering_miniature():
    """GLR-CUCB < M-Exp3 < random on piecewise AoI regret (Fig. 2a)."""
    env = random_piecewise_env(KEY, 5, 5000, 5)
    regrets = {}
    for sched in [RandomScheduler(5, 2), MExp3(5, 2),
                  GLRCUCB(5, 2, history=512, detector_stride=4)]:
        out = simulate_aoi_regret(sched, env, KEY, 5000)
        regrets[sched.name] = float(out["final_regret"])
    assert regrets["glr-cucb"] < regrets["m-exp3"] < regrets["random"]


def test_full_fl_pipeline_then_serve():
    """Train a smoke-size qwen on synthetic tokens through the FL round at
    pod-free scale (host mesh), then serve greedily from the result."""
    from repro.core.channels import make_stationary
    from repro.launch.steps import (
        make_fl_train_step, make_serve_step, make_train_state_init)
    from repro.optim import adamw

    cfg = get_smoke_config("qwen1.5-0.5b")
    model = build_model(cfg, remat="none")
    n_clients = 4
    sched = GLRCUCB(8, n_clients, history=64)
    env = make_stationary(jnp.linspace(0.95, 0.4, 8))
    opt = adamw(1e-3)
    init_fn = make_train_state_init(model, opt, sched, n_clients)
    state = init_fn(KEY)
    step = jax.jit(make_fl_train_step(model, opt, sched, env, n_clients))

    batch = {"tokens": jax.random.randint(KEY, (8, 32), 0, cfg.vocab_size)}
    losses = []
    for t in range(8):
        state, mets = step(state, batch, jax.random.fold_in(KEY, t))
        losses.append(float(mets["loss"]))
        assert np.isfinite(losses[-1])
        assert float(mets["mean_aoi"]) >= 1.0
    assert losses[-1] < losses[0]          # same batch -> loss must drop

    serve = jax.jit(make_serve_step(model))
    cache = model.init_cache(8, 16)
    tok = jnp.zeros((8,), jnp.int32)
    for _ in range(4):
        tok, cache = serve(state.params, cache, tok)
    assert tok.shape == (8,) and int(cache["pos"]) == 4


def test_input_specs_cover_all_arch_shape_pairs():
    """Deliverable (e)/(f) metadata path: every supported (arch x shape)
    produces well-formed sharded ShapeDtypeStructs on the production mesh
    topology (abstract mesh — no devices needed)."""
    from jax.sharding import AbstractMesh
    from repro.configs import get_config
    from repro.launch.specs import SHAPES, batch_specs, cache_specs, supported
    mesh = AbstractMesh((16, 16), ("data", "model"))
    n_ok = n_skip = 0
    for arch in list_archs():
        cfg = get_config(arch)
        model = build_model(cfg)
        for shape_name, shape in SHAPES.items():
            ok, reason = supported(cfg, shape_name)
            if not ok:
                assert cfg.is_encoder
                n_skip += 1
                continue
            bs = batch_specs(cfg, shape, mesh)
            assert all(hasattr(v, "shape") for v in bs.values())
            if shape.mode == "decode":
                cs = cache_specs(model, shape, mesh)
                assert "pos" in cs
            n_ok += 1
    assert n_skip == 2                      # hubert x {decode_32k, long_500k}
    assert n_ok == 42                       # 11 archs x 4 shapes - 2
