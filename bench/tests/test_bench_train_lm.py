"""The federated LoRA cell's body at smoke widths on the CPU: two rounds
of ``SparseAsyncFLTrainer.run`` over the LoRA loss follow the reference's
replay, each fault the cell can have reads at least ten times a sound
run on some check, and the readers and work counts hold together.

The architecture's smoke config stands in for the published widths (the
cell's ``model_config`` is patched); everything else runs as on the chip.
"""
import argparse
import dataclasses
import os
import sys
import time
from unittest import mock

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

BENCH = harness.Benchmark(ROOT)
KIND = BENCH.kind("train_lm")


def _smoke_cfg():
    from repro.configs import get_smoke_config
    sm = get_smoke_config("deepseek-v2-lite")
    cfg = BENCH.config("dsv2lite-fedlora")
    cfg.update({"num_hidden_layers": 2, "vocab_size": sm.vocab_size,
                "n_routed_experts": 2, "hidden_size": sm.d_model,
                "num_attention_heads": sm.n_heads,
                "qk_nope_head_dim": sm.qk_nope_dim,
                "qk_rope_head_dim": sm.qk_rope_dim, "v_head_dim": sm.v_head_dim,
                "kv_lora_rank": sm.kv_lora_rank,
                "intermediate_size": sm.d_ff,
                "moe_intermediate_size": sm.d_expert,
                "n_shared_experts": sm.n_shared_experts,
                "first_k_dense_replace": sm.first_k_dense,
                "num_experts_per_tok": sm.experts_per_token,
                "rope_scaling": dict(cfg["rope_scaling"],
                                     original_max_position_embeddings=16)})
    cfg["held_experts"] = {"first": 1, "routed_over": sm.n_experts}
    cfg["lora"] = dict(cfg["lora"], rank=4, alpha=8)
    cfg["population"] = dict(cfg["population"], n_clients=40,
                             sequences_per_client=4, tokens_per_sequence=13)
    cfg["round"] = dict(cfg["round"], n_sched=4, proxy_sequences=4,
                        proxy_chunk=2, rounds_per_call=1)
    cfg["scheduler"] = dict(cfg["scheduler"], n_channels=6, n_clients=4,
                            history=16)
    return cfg, sm


def _smoke_model_config(sm, dtype):
    def made(cfg):
        return dataclasses.replace(
            sm, dtype=dtype, n_layers=cfg["num_hidden_layers"],
            vocab_size=cfg["vocab_size"],
            held_first=cfg["held_experts"]["first"],
            held_count=cfg["n_routed_experts"], router_aux_weight=0.0,
            lora_rank=cfg["lora"]["rank"],
            lora_alpha=float(cfg["lora"]["alpha"]))
    return made


def _ctx(cfg, seed=2 ** 33 + 17):
    import jax
    return argparse.Namespace(bench=BENCH, cfg=cfg, mix=BENCH.traffic("fl_lm_mean"),
                              seed=seed, seconds=1, trace=False,
                              devices=jax.devices(), t_start=time.perf_counter(),
                              log=lambda m: None)


def _readings(dtype="float32", fault=None):
    cfg, sm = _smoke_cfg()
    with mock.patch.object(KIND, "model_config", _smoke_model_config(sm, dtype)):
        if fault is None:
            return KIND.run(_ctx(cfg))
        with KIND.FAULTS[fault]():
            return KIND.run(_ctx(cfg))


@pytest.fixture(scope="module")
def sound():
    return _readings()


def test_two_rounds_follow_the_reference(sound):
    checks = {k: c["value"] for k, c in sound["checks"].items()}
    assert checks["dropped_tokens"] == 0
    assert checks["loss_gap"] < 1e-4 and checks["update_gap"] < 1e-3
    assert checks["change_gap"] < 1e-3 and checks["contrib_gap"] < 1e-3
    assert sound["e2e"]["fl_rounds_per_s"] > 0
    counters = sound["obs"]["counters"]
    assert counters["rounds"] > 0 and len(counters["routed"]) == 2


@pytest.mark.parametrize("fault", sorted(KIND.FAULTS))
def test_each_fault_reads_ten_times_a_sound_run(sound, fault):
    got = {k: c["value"] for k, c in _readings(fault=fault)["checks"].items()}
    base = {k: c["value"] for k, c in sound["checks"].items()}
    assert any(got[k] >= 10 * base[k] and got[k] > 0 for k in got), (got, base)


def test_the_program_reads_like_the_reference_in_discrete_state():
    cfg, sm = _smoke_cfg()
    with mock.patch.object(KIND, "model_config", _smoke_model_config(sm, "bfloat16")):
        rows = KIND.control(_ctx(cfg), [2 ** 32 + 9])
    row = rows[0]
    assert all(v == 0 for v in row["look"]["differ"].values()), row["look"]
    assert row["program"]["dropped_tokens"] == 0
    # the bfloat16 control loses the adapters' small steps
    assert row["control_bf16"]["change_gap"] > 10 * row["program"]["change_gap"]


def test_the_replay_goes_on_from_the_given_estimate():
    """Round 2 selects by the estimate handed over after round 1, while
    the replay reports its own round-1 estimate."""
    import jax.numpy as jnp
    from repro.models import build_model
    from bench.kinds.train import round_keys
    cfg, sm = _smoke_cfg()
    ref = BENCH.reference("dsv2lite-fedlora")
    model = build_model(_smoke_model_config(sm, "float32")(cfg))
    inp = KIND.make_inputs(cfg, 2 ** 32 + 9, model, ref)
    keys = list(round_keys(inp["keys"], 2, 1).reshape(2, 2))
    data = (inp["client_x"], inp["proxy_x"], inp["means"], inp["breaks"])

    def go(estimates):
        return ref.replay(inp["adapters"], inp["frozen"], data, keys, cfg,
                          jnp.float32, estimates=estimates)
    own = go(None)
    sel1, rows1 = own[5][0]
    n = cfg["population"]["n_clients"]
    # the highest unselected id gets every other client's estimate beaten
    out = max(set(range(n)) - set(sel1.tolist()))
    contrib = np.ones(n, np.float32)
    contrib[sel1] = rows1
    contrib[out] = 10 * rows1.max()
    forced = go([(contrib, np.full(n, 1 / len(sel1), np.float32)), None])
    np.testing.assert_array_equal(forced[5][0][0], sel1)
    np.testing.assert_array_equal(forced[5][0][1], rows1)
    assert out in forced[5][1][0].tolist()
    assert out not in own[5][1][0].tolist()
    gap = KIND.contrib_gap([None, forced[5][1]], own[5])
    assert gap > 0 and KIND.contrib_gap(own[5], own[5]) == 0


def test_an_unknown_architecture_fails_before_any_work():
    cfg = BENCH.config("dsv2lite-fedlora")
    cfg["arch"] = "no-such-arch"
    with pytest.raises(KeyError):
        KIND.model_config(cfg)


def test_the_configuration_resolves_to_the_published_widths():
    cfg = BENCH.config("dsv2lite-fedlora")
    mc = KIND.model_config(cfg)
    assert (mc.d_model, mc.n_experts, mc.n_held_experts, mc.n_layers,
            mc.vocab_size) == (2048, 64, 8, 5, 12800)
    from repro.models import build_model, lora
    import jax
    frozen = jax.eval_shape(lambda k: build_model(mc).init(k)[0],
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(v.shape)) for v in frozen.values()) == 535_060_992
    adapters = jax.eval_shape(lambda f: lora.init_adapters(
        f, mc, jax.random.PRNGKey(0)), frozen)
    assert sum(int(np.prod(v.shape)) for v in adapters.values()) == \
        cfg["lora"]["params"] == 2_631_680
    assert cfg["lora"]["params"] % 2048 == 0


def test_the_reference_draws_the_programs_layout_at_the_published_widths():
    import jax
    from repro.models import build_model, lora
    cfg = BENCH.config("dsv2lite-fedlora")
    mc = KIND.model_config(cfg)
    key = jax.random.PRNGKey(0)
    base, adapters = jax.eval_shape(
        lambda k: BENCH.reference("dsv2lite-fedlora").init(cfg, k, k), key)
    like = jax.eval_shape(lambda k: build_model(mc).init(k)[0], key)
    like_adapters = jax.eval_shape(
        lambda f: lora.init_adapters(f, mc, key), like)
    for got, want in ((base, like), (adapters, like_adapters)):
        assert {k: (v.shape, v.dtype) for k, v in got.items()} == \
            {k: (v.shape, v.dtype) for k, v in want.items()}


def test_work_counts_at_the_published_widths():
    cfg = BENCH.config("dsv2lite-fedlora")
    work = BENCH.work("dsv2lite_fedlora")
    trained = 20 * 4 * 2 * 512
    # 6 picks of 64 experts, 8 held, over 4 MoE layers: 3 a token on average
    f = work.forward_flops(cfg, 3.0)
    assert 5.2e8 < f < 5.6e8
    flops = work.round_flops(cfg, 3.0 * trained)
    assert flops == pytest.approx(trained * (2 * f + work.lora_grad_flops(cfg))
                                  + trained * f)
    hf, hb = work.held_experts_work(cfg, 3.0 * trained)
    assert hf == pytest.approx(9 * 3.0 * trained * 2 * 2048 * 1408)
    assert hb > 0


class _Trace:
    window_s = 2.0

    def module_time_s(self, name):
        return (1.0, 2) if name == "jit__run_plain" else (0.0, 0)

    def op_time_s(self, contains):
        return (0.5, 40) if contains == "ragged-dot" else (0.0, 0)


def test_the_readers():
    cfg = BENCH.config("dsv2lite-fedlora")
    obs = {"trace": _Trace(), "cfg": cfg, "bench": BENCH,
           "device_kind": "TPU v5 lite",
           "counters": {"routed": [4.0, 2.0, 2.0, 0.0, 2.0, 2.0, 2.0, 2.0],
                        "absent": 0, "dropped": 0, "rounds": 2}}
    names = {m["name"] for m in BENCH.per_layer("dsv2lite-fedlora.mean")}
    assert names == {"fl_round_device_ms.dsv2lite", "device_idle_share.dsv2lite",
                     "fl_lm_mfu", "expert_load_imbalance.dsv2lite",
                     "held_experts_roofline.dsv2lite"}
    assert BENCH.metric_reader("expert_load_imbalance.dsv2lite").read(
        obs, None) == pytest.approx(2.0)
    assert BENCH.metric_reader("fl_round_device_ms.dsv2lite").read(
        obs, None) == pytest.approx(500.0)
    assert BENCH.metric_reader("fl_lm_mfu").read(obs, None) > 0
    assert BENCH.metric_reader("held_experts_roofline.dsv2lite").read(obs, None) > 0
    obs["counters"] = None
    for name in ("fl_lm_mfu", "expert_load_imbalance.dsv2lite",
                 "held_experts_roofline.dsv2lite"):
        assert BENCH.metric_reader(name).read(obs, None) is None
