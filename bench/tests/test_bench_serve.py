"""The serving cells' body at tiny sizes on the CPU: a sound run is
correct, the control and each fault the cell can have are caught.

The harness's look for a chip is skipped (the cell's ``run`` is called
directly); everything after it runs as on the chip, with the timed path
broken underneath for the fault cases.
"""
import argparse
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

BENCH = harness.Benchmark(ROOT)


def _ctx(traffic, seed=2 ** 31 + 99):
    import jax
    cfg = BENCH.config("serve256")
    cfg["server"] = dict(cfg["server"], capacity=12, slots=4)
    cfg["scheduler"] = dict(cfg["scheduler"], history=16)
    mix = dict(BENCH.traffic(traffic), jobs=12, warmup_s=0.3)
    if traffic == "think":
        mix["think_scale_s"] = 0.01
        mix["job_rounds"] = 20
    return argparse.Namespace(bench=BENCH, cfg=cfg, mix=mix, seed=seed,
                              seconds=1, trace=False, devices=jax.devices(),
                              t_start=time.perf_counter(),
                              log=lambda msg: None)


@pytest.fixture
def fresh_programs():
    """Sound runs build their serve steps anew too (the process keeps
    compiled steps)."""
    from repro.sim.sweep import clear_sweep_cache
    clear_sweep_cache()
    yield
    clear_sweep_cache()


@pytest.mark.parametrize("traffic", ["think", "sat"])
def test_sound_run_is_correct(traffic, fresh_programs):
    ctx = _ctx(traffic)
    out = BENCH.kind("serve").run(ctx)
    assert harness.correct(out["checks"]), out["checks"]
    assert out["checks"]["mismatch_share"]["value"] == 0.0
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = out["e2e"]
    assert e2e["setup_s"] > 0 and e2e["decisions_per_s"] > 0
    assert e2e["decision_p99_ms"] > 0
    if traffic == "think":      # jobs left and joined, and were checked
        assert out["obs"]["loop"].left_jobs > 0


@pytest.mark.parametrize("fault,traffic", [("state_unchanged", "sat"),
                                           ("half_batch", "sat"),
                                           ("altered_answer", "think")])
def test_each_fault_is_caught(fault, traffic):
    kind = BENCH.kind("serve")
    with kind.FAULTS[fault]():
        out = kind.run(_ctx(traffic))
    assert not harness.correct(out["checks"]), out["checks"]


def test_the_control_fails_the_limit(fresh_programs):
    """The reference in bfloat16, in the service's place, departs from the
    float32 reference by more than the limit."""
    ctx = _ctx("sat")
    ctx.seconds = 1
    rows = BENCH.kind("serve").control(ctx, [2 ** 31 + 3])
    limit = ctx.cfg["check"]["mismatch_share_limit"]
    assert rows[0]["service"] <= limit
    assert rows[0]["control_bf16"] > 3 * limit
