"""Rehearsal of ``chip_smoke.py`` on the CPU at tiny sizes.

The script's entry point refuses to run without a TPU; its phases are
functions of their sizes, so the control flow and every parity check run
here with the kernels in interpret mode (phase 2) or on their jnp oracles
(the auto dispatch of phases 3-5 off-TPU).  The four-chip phase runs on
whatever devices JAX has (one here: a 1-device mesh, which must also be
bitwise equal to the unsharded paths).
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_entry_point_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_kernel_phase_tiny():
    chip_smoke.phase_kernels(backend="pallas_interpret", n=5, h=128, g=3,
                             gh=64, m=6, p=4096, chunk=1024)


def test_serve_phase_tiny():
    chip_smoke.phase_serve(capacity=8, slots=4, n=6, m=2, h=32, n_req=48,
                           t_par=30, expect_kernel=False)


def test_trainer_phase_tiny():
    chip_smoke.phase_trainer(n=200, m=8, nch=6, d=4, nex=4, bsz=2,
                             rounds=2, pn=20, pnch=30, pr=6,
                             expect_kernel=False)


def test_sweep_phase_tiny():
    chip_smoke.phase_sweep(n=4, m=2, h=64, horizon=200, seeds=1,
                           expect_kernel=False)


def test_four_chip_phase_tiny():
    chip_smoke.phase_four_chips(tenants=16, slots=4, n=6, m=2, h=32,
                                sweep_n=4, sweep_h=64, horizon=200,
                                expect_kernel=False)
