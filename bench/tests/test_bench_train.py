"""The training cells' body at tiny sizes on the CPU: a sound run is
correct, the control and each fault the cell can have are caught.

The harness's look for a chip is skipped (the cell's ``run`` is called
directly); everything after it runs as on the chip, at widths small
enough for the CPU, with the timed path broken underneath for the fault
cases.
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


def _ctx(traffic, seed=2 ** 31 + 77):
    import jax
    cfg = BENCH.config("fedcnn-pop")
    cfg["model"] = dict(cfg["model"], conv1=4, conv2=8, fc=16)
    cfg["population"] = dict(cfg["population"], n_clients=48,
                             examples_per_client=16)
    cfg["round"] = dict(cfg["round"], n_sched=4, proxy_examples=8)
    cfg["scheduler"] = dict(cfg["scheduler"], n_channels=6, n_clients=4,
                            history=16)
    return argparse.Namespace(bench=BENCH, cfg=cfg, mix=BENCH.traffic(traffic),
                              seed=seed, seconds=1, trace=False,
                              devices=jax.devices(),
                              t_start=time.perf_counter(), log=lambda m: None)


@pytest.mark.parametrize("traffic", ["fl_mean", "fl_median"])
def test_sound_run_is_correct(traffic):
    out = BENCH.kind("train").run(_ctx(traffic))
    assert harness.correct(out["checks"]), out["checks"]
    assert out["e2e"]["fl_rounds_per_s"] > 0 and out["e2e"]["setup_s"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_aggregate"])
def test_each_fault_is_caught(fault):
    kind = BENCH.kind("train")
    with kind.FAULTS[fault]():
        out = kind.run(_ctx("fl_median" if fault == "altered_aggregate"
                            else "fl_mean"))
    assert not harness.correct(out["checks"]), out["checks"]


def test_the_control_fails_the_limits():
    ctx = _ctx("fl_mean")
    rows = BENCH.kind("train").control(ctx, [2 ** 31 + 3])
    limits = ctx.cfg["check"]
    assert all(v <= limits[f"{k}_limit"] for k, v in rows[0]["program"].items())
    assert any(v > 3 * limits[f"{k}_limit"]
               for k, v in rows[0]["control_bf16"].items())
    # the look: a sound run parts from the reference in no discrete state
    look = rows[0]["look"]
    assert set(look["differ"]) == {"slot_clients", "has_update", "aoi"}
    assert look["differ"]["slot_clients"] == 0
    assert len(look["change_leaves"]) == 8 and len(look["loss_gaps"]) == 3
