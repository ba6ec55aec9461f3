"""Spans and latency counters of the scheduling service (``repro.sim.serve``).

Contracts under test:

* under a profiler session, every ``serve_stream`` step opens one
  ``sched.take_batch``, ``sched.pack``, ``sched.dispatch``, ``sched.fetch``
  and ``sched.deliver`` span, with its step number and batch size as
  metadata; every ``join``/``leave`` one ``sched.admit``; and a cold
  ``warm()`` one ``sched.compile`` per ladder size;
* while a profiler session is on, the queue-wait and in-flight histograms
  count every streamed request exactly once, in the buckets its times fall
  in, whether binned mid-stream or by ``stats()``; with none on, nothing;
* a ``stats()`` snapshot is not changed by later steps;
* the answers are bitwise the same with a profiler session on and off.
"""
import copy
import glob
import os
import time

import jax
import numpy as np
import pytest

import repro.sim.serve as serve_mod
from repro.core.bandits import GLRCUCB
from repro.sim import SchedServer, ServeRequest
from repro.sim.serve import LATENCY_EDGES_S, latency_quantile
from repro.sim.sweep import clear_sweep_cache

KEY = jax.random.PRNGKey(0)
N, M = 6, 2
PHASES = ("sched.take_batch", "sched.pack", "sched.dispatch", "sched.fetch",
          "sched.deliver")


def _mk_server(**kw):
    cfg = dict(capacity=8, slots=4, use_matching=False)
    cfg.update(kw)
    sched = GLRCUCB(N, M, history=64, detector_stride=3, min_samples=4)
    return SchedServer(sched, **cfg)


def _requests(tenants, n, seed=1):
    states = np.asarray(jax.random.bernoulli(
        jax.random.fold_in(KEY, seed), 0.6, (n, N)), np.float32)
    keys = np.asarray(jax.random.split(jax.random.fold_in(KEY, seed + 1), n))
    return [ServeRequest(tenants[j % len(tenants)], states[j], keys[j])
            for j in range(n)]


def _host_spans(trace_dir):
    """``(name, metadata)`` of every ``sched.*`` host span in the trace."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sched."):
                    out.append((ev.name, dict(ev.stats)))
    return out


def _churning_source(server, reqs, tenants):
    """The requests in flushed segments of 5, one leave and re-join after
    each segment; returns the source and a count of admits it made."""
    admits = [0]

    def source():
        for s in range(0, len(reqs), 5):
            yield from reqs[s:s + 5]
            yield None
            t = tenants[(s // 5) % len(tenants)]
            server.leave(t)
            server.join(t, key=jax.random.fold_in(KEY, 100 + s))
            admits[0] += 2
    return source(), admits


def test_stream_opens_one_span_per_phase_per_step(tmp_path):
    tenants = [f"t{i}" for i in range(5)]
    server = _mk_server()
    server.warm()
    reqs = _requests(tenants, 23)
    with jax.profiler.trace(str(tmp_path)):
        for i, t in enumerate(tenants):
            server.join(t, key=jax.random.fold_in(KEY, i))
        src, admits = _churning_source(server, reqs, tenants)
        got = list(server.serve_stream(src, autosize=True))
    assert len(got) == len(reqs)
    steps = server.stats()["stream_steps"]
    spans = _host_spans(str(tmp_path))
    names = [n for n, _ in spans]
    for phase in PHASES:
        assert names.count(phase) == steps, phase
    assert names.count("sched.admit") == len(tenants) + admits[0]
    assert 1 <= names.count("sched.source") <= steps + 1
    assert "sched.compile" not in names          # the ladder was warm
    dispatch = [meta for n, meta in spans if n == "sched.dispatch"]
    assert sorted(m["step"] for m in dispatch) == list(range(steps))
    assert {m["b"] for m in dispatch} <= set(server._ladder)


def test_cold_warm_opens_one_compile_span_per_ladder_size(tmp_path):
    clear_sweep_cache()
    with jax.profiler.trace(str(tmp_path)):
        server = _mk_server(slots=6)
        server.warm()
    compiles = [meta for n, meta in _host_spans(str(tmp_path))
                if n == "sched.compile"]
    sizes = sorted(m["b"] for m in compiles if "b" in m)
    assert sizes == server._ladder == [1, 2, 4, 6]
    assert len(compiles) == len(server._ladder) + 1   # and the admit program
    assert server.compiles == len(compiles)


@pytest.mark.parametrize("bin_after", [serve_mod._LATENCY_BIN_AFTER, 3])
def test_histograms_count_each_request_once(tmp_path, monkeypatch, bin_after):
    """Segments of 3 requests, each pulled 20 ms before its flush: every
    request waits at least 20 ms in the queue, and every step but the last
    is in flight while the next segment is pulled.  A small
    ``_LATENCY_BIN_AFTER`` bins mid-stream; the counts are the same."""
    monkeypatch.setattr(serve_mod, "_LATENCY_BIN_AFTER", bin_after)
    tenants = [f"t{i}" for i in range(3)]
    server = _mk_server()
    for i, t in enumerate(tenants):
        server.join(t, key=jax.random.fold_in(KEY, i))
    server.warm()
    reqs = _requests(tenants, 12)

    def source():
        for s in range(0, len(reqs), 3):
            yield from reqs[s:s + 3]
            time.sleep(0.02)
            yield None

    with jax.profiler.trace(str(tmp_path)):
        assert len(list(server.serve_stream(source()))) == 12
    st = server.stats()
    edges = np.asarray(st["latency_edges_s"])
    np.testing.assert_array_equal(edges, LATENCY_EDGES_S)
    assert edges[0] == pytest.approx(1e-6) and edges[-1] == pytest.approx(100)
    assert np.all(edges[1:] / edges[:-1] <= 1.1)
    wait = np.asarray(st["queue_wait_counts"])
    flight = np.asarray(st["inflight_counts"])
    assert wait.sum() == flight.sum() == 12 == st["served"]
    # bucket i holds [edges[i-1], edges[i]): count the buckets at or above
    # the one that holds 20 ms
    at_20ms = np.searchsorted(edges, 0.02, side="right")
    assert wait[at_20ms:].sum() == 12
    assert flight[at_20ms:].sum() == 9
    assert latency_quantile(edges, wait, 50) >= edges[at_20ms - 1]


def test_histograms_count_deferred_duplicates_once(tmp_path):
    """A tenant's second request in one batch is deferred to a later step,
    and each request is still counted once."""
    server = _mk_server()
    for i, t in enumerate(("a", "b")):
        server.join(t, key=jax.random.fold_in(KEY, i))
    server.warm()
    reqs = _requests(["a", "a", "b", "a", "b", "b", "a"], 21)
    with jax.profiler.trace(str(tmp_path)):
        assert len(list(server.serve_stream(iter(reqs)))) == 21
    st = server.stats()
    assert sum(st["queue_wait_counts"]) == sum(st["inflight_counts"]) == 21
    assert st["stream_steps"] > 21 // server.slots


def test_histograms_count_nothing_with_no_profiler_session():
    tenants = [f"t{i}" for i in range(4)]
    server = _mk_server()
    for i, t in enumerate(tenants):
        server.join(t, key=jax.random.fold_in(KEY, i))
    server.warm()
    assert len(list(server.serve_stream(iter(_requests(tenants, 9))))) == 9
    st = server.stats()
    assert st["served"] == 9
    assert sum(st["queue_wait_counts"]) == sum(st["inflight_counts"]) == 0
    assert latency_quantile(st["latency_edges_s"], st["queue_wait_counts"],
                            99) is None


def test_stats_snapshot_is_not_changed_by_later_steps(tmp_path):
    tenants = [f"t{i}" for i in range(4)]
    server = _mk_server()
    for i, t in enumerate(tenants):
        server.join(t, key=jax.random.fold_in(KEY, i))
    server.warm()
    with jax.profiler.trace(str(tmp_path)):
        list(server.serve_stream(iter(_requests(tenants, 8))))
        snap = server.stats()
        kept = copy.deepcopy(snap)
        list(server.serve_stream(iter(_requests(tenants, 8, seed=5))))
    assert snap == kept
    later = server.stats()
    assert sum(later["queue_wait_counts"]) == 16
    assert sum(snap["queue_wait_counts"]) == 8


def test_answers_are_bitwise_equal_with_a_profiler_session_on(tmp_path):
    tenants = [f"t{i}" for i in range(5)]
    reqs = _requests(tenants, 31, seed=9)

    def run(traced):
        server = _mk_server()
        for i, t in enumerate(tenants):
            server.join(t, key=jax.random.fold_in(KEY, i))
        server.warm()
        src, _ = _churning_source(server, reqs, tenants)
        if traced:
            with jax.profiler.trace(str(tmp_path)):
                out = list(server.serve_stream(src, autosize=True))
        else:
            out = list(server.serve_stream(src, autosize=True))
        return out

    plain, traced = run(False), run(True)
    assert [i for i, _ in plain] == [i for i, _ in traced]
    for (_, a), (_, b) in zip(plain, traced):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("q, want", [
    (50, 1.5e-3),                 # midway through the bucket [1, 2) ms
    (100, 2e-3),                  # the top of the last non-empty bucket
    (0, 1e-3),                    # the bottom of the first non-empty one
])
def test_latency_quantile_interpolates_in_its_bucket(q, want):
    edges = [1e-3, 2e-3, 4e-3]
    counts = [0, 10, 0, 0]        # ten times in [1 ms, 2 ms)
    assert latency_quantile(edges, counts, q) == pytest.approx(want)


def test_latency_quantile_of_empty_and_open_buckets():
    edges = [1e-3, 2e-3]
    assert latency_quantile(edges, [0, 0, 0], 99) is None
    assert latency_quantile(edges, [0, 0, 5], 99) == pytest.approx(2e-3)
    assert latency_quantile(edges, [4, 0, 0], 50) == pytest.approx(0.5e-3)
