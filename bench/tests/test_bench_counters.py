"""The service's latency histograms and host spans as the benchmark reads
them, on the CPU: the window's histogram, its percentile, the readers of
``queue_wait_p99_ms`` and ``inflight_p99_ms``, and a traced serving run at
tiny sizes reduced by ``bench.spans.SpanTrace``."""
import argparse
import glob
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench.counters import percentile, window_histogram  # noqa: E402

BENCH = harness.Benchmark(ROOT)
EDGES = [1e-3, 2e-3, 4e-3]


def _obs(start, end):
    return {"counters": {"start": start, "end": end}}


def _marks(before, after, key="queue_wait_counts"):
    return _obs({"latency_edges_s": EDGES, key: before},
                {"latency_edges_s": EDGES, key: after})


def test_the_window_is_the_difference_of_the_marks():
    edges, counts = window_histogram(_marks([0, 1, 0, 0], [0, 11, 0, 2]),
                                     "queue_wait_counts")
    np.testing.assert_array_equal(edges, EDGES)
    np.testing.assert_array_equal(counts, [0, 10, 0, 2])


@pytest.mark.parametrize("q, want_ms", [
    (50, 1.6),          # rank 6 of the ten in [1, 2) ms
    (99, 4.0),          # in the open last bucket: its edge
    (0, 1.0),           # the bottom of the first non-empty bucket
])
def test_percentile_interpolates_in_its_bucket(q, want_ms):
    counts = np.array([0, 10, 0, 2], np.float64)
    got = percentile(np.asarray(EDGES), counts, q)
    assert got * 1e3 == pytest.approx(want_ms)


@pytest.mark.parametrize("metric, key", [
    ("queue_wait_p99_ms.serve_think", "queue_wait_counts"),
    ("inflight_p99_ms.serve_think", "inflight_counts"),
])
def test_readers_read_their_histogram_and_nothing_else(metric, key):
    reader = BENCH.metric_reader(metric)
    obs = _marks([0, 0, 0, 0], [0, 0, 100, 0], key)
    assert reader.read(obs, {}) == pytest.approx(3.98)   # 2 + 2 * 0.99
    # a service whose stats() has no histograms, and an empty window
    assert reader.read(_obs({"served": 0}, {"served": 5}), {}) is None
    assert reader.read(_marks([0, 3, 0, 0], [0, 3, 0, 0], key), {}) is None
    assert reader.read(_obs({}, {}), {}) is None


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """The think cell at tiny sizes, run under the profiler, with its
    trace kept."""
    import jax
    from repro.sim.sweep import clear_sweep_cache
    clear_sweep_cache()
    cfg = BENCH.config("serve256")
    cfg["server"] = dict(cfg["server"], capacity=12, slots=4)
    cfg["scheduler"] = dict(cfg["scheduler"], history=16)
    mix = dict(BENCH.traffic("think"), jobs=12, warmup_s=0.3,
               think_scale_s=0.01, job_rounds=20)
    kind = BENCH.kind("serve")
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    loop, server, _ = kind.drive(cfg, mix, 2 ** 31 + 7, 1, trace_dir=trace_dir)
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    clear_sweep_cache()
    obs = {"counters": loop.window_marks, "loop": loop, "cfg": cfg,
           "bench": BENCH}
    return obs, path, jax


def test_a_traced_run_counts_every_request_dispatched_in_the_window(traced_run):
    obs, _, _ = traced_run
    start, end = obs["counters"]["start"], obs["counters"]["end"]
    _, waits = window_histogram(obs, "queue_wait_counts")
    assert waits.sum() == end["served"] - start["served"] > 0
    for metric in ("queue_wait_p99_ms.serve_think",
                   "inflight_p99_ms.serve_think"):
        value = BENCH.metric_reader(metric).read(obs, {})
        assert value is not None and 0 < value < 1e4, metric


def test_a_traced_run_reduces_to_spans_per_step(traced_run):
    from bench.spans import SpanTrace
    obs, path, _ = traced_run
    trace = SpanTrace.from_file(path)
    start, end = obs["counters"]["start"], obs["counters"]["end"]
    steps = end["stream_steps"] - start["stream_steps"]
    for name in ("sched.take_batch", "sched.pack", "sched.dispatch"):
        seconds, count = trace.self_time_s(name)
        assert abs(count - steps) <= 1 and seconds > 0, name
    # every job that left and joined in the window ran one admit each way
    _, admits = trace.self_time_s("sched.admit")
    assert admits > 0 and admits % 2 == 0
    _, generated = trace.self_time_s("bench.generate")
    assert generated > 0
    assert trace.idle_by_span() == {}            # no chip in this trace
