"""Work counts of the kernels, checked against hand counts (CPU only)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.harness import Benchmark  # noqa: E402


def test_glr_step_work_at_the_service_shapes():
    work = Benchmark(ROOT).work("glr_step").work
    # 64 tenants x 30 channels, 256-sample windows: 37 ops per split and
    # 6 per append; the windows read once (4 bytes a sample) plus 8 words
    # per channel
    flops, bytes_ = work(64, 30, 256)
    assert flops == 64 * 30 * (256 * 37 + 6) == 18_197_760
    assert bytes_ == 4 * 64 * 30 * (256 + 8) == 2_027_520
    # linear in the tenants
    assert work(1, 30, 256) == (flops // 64, bytes_ // 64)


def test_peaks_are_keyed_by_device_kind():
    bench = Benchmark(ROOT)
    peaks = bench.peaks("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    import pytest
    with pytest.raises(KeyError):
        bench.peaks("TPU v9 imaginary")


def test_fedavg_cnn_work_at_its_published_widths():
    bench = Benchmark(ROOT)
    cnn = bench.work("fedavg_cnn")
    cfg = bench.config("fedcnn-pop")
    assert cnn.params(cfg["model"]) == 1_663_370 == cfg["model"]["params"]
    # multiply-adds: conv1 28*28*32*25, conv2 14*14*64*25*32, FC 3136*512,
    # FC 512*10
    macs = 627_200 + 10_035_200 + 1_605_632 + 5_120
    assert cnn.forward_flops(cfg["model"]) == 2 * macs == 24_546_304
    # a round: 20 clients x 6 steps x 10 images, 3x forward; 20 leave-one-out
    # models x 100 proxy images, 1x forward
    assert cnn.round_flops(cfg) == 2 * macs * (3 * 1200 + 2000)


def test_aggregation_kernel_work():
    bench = Benchmark(ROOT)
    m, p = 20, 1_663_370
    assert bench.work("weighted_aggregate").work(m, p) == (
        2 * m * p, 4 * (m * p + m + p))
    # ranking by pairwise comparison: 20 * 20 + 2 * 20 + 1 = 441 a coordinate
    assert bench.work("robust_trimmed").work(m, p) == (
        441 * p, 4 * (m * p + m + p))


def test_reference_model_has_the_published_parameter_count():
    import jax
    import numpy as np
    bench = Benchmark(ROOT)
    cfg = bench.config("fedcnn-pop")
    shapes = jax.eval_shape(
        lambda: bench.reference("fedcnn-pop").init(jax.random.PRNGKey(0),
                                                   cfg["model"]))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 1_663_370
