"""The ``sched.*`` span reduction on a small synthetic trace (CPU only)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.spans import UNATTRIBUTED, SpanTrace, span_name  # noqa: E402
from bench.tracing import Trace  # noqa: E402

META = "#step=3,b=64#"


def _events(lines):
    """Text-proto lines of one plane from ``{line: [(name, start_ns,
    dur_ns)]}``, with the metadata the events refer to."""
    names, out = {}, []
    for i, (line, evs) in enumerate(lines.items(), 1):
        body = []
        for name, start, dur in evs:
            mid = names.setdefault(name, len(names) + 1)
            body.append(f"events {{ metadata_id: {mid} offset_ps: {start * 1000}"
                        f" duration_ps: {dur * 1000} }}")
        out.append(f'lines {{ id: {i} name: "{line}" timestamp_ns: 0 '
                   + " ".join(body) + " }")
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for n, i in names.items())
    return " ".join(out) + " " + meta


@pytest.fixture(scope="module")
def profile():
    """Device busy over [1000, 1500) and [6000, 6500) of the window
    [0, 10000): idle gaps [0, 1000), [1500, 6000) and [6500, 10000).  One
    serve-loop iteration of nested host spans, and one span after the
    window."""
    from jax.profiler import ProfileData
    device = _events({
        "XLA Modules": [("jit_serve_step(1)", 1000, 500),
                        ("jit_serve_step(1)", 6000, 500)],
        "XLA Ops": [("%fusion.1 = f32[2] fusion()", 1000, 500),
                    ("%fusion.2 = f32[2] fusion()", 6000, 500)],
    })
    host = _events({
        "main": [("bench.window", 0, 10000),
                 ("sched.source" + META, 200, 2200),
                 ("bench.generate", 300, 500),
                 ("bench.sleep", 1200, 1000),
                 ("sched.take_batch" + META, 2400, 200),
                 ("sched.pack" + META, 2600, 800),
                 ("sched.dispatch" + META, 3400, 1600),
                 ("sched.compile#b=64#", 3500, 1000),
                 ("sched.fetch" + META, 5000, 200),
                 ("sched.deliver" + META, 5200, 1800),
                 ("bench.retire", 5300, 500),
                 ("bench.retire", 6200, 600),
                 ("sched.pack" + META, 10500, 200)],   # after the window
    })
    text = (f'planes {{ id: 1 name: "/device:TPU:0" {device} }} '
            f'planes {{ id: 2 name: "/host:CPU" {host} }}')
    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def trace(profile):
    return SpanTrace(profile)


def test_span_names_lose_their_metadata(trace):
    assert span_name("sched.pack#step=3,b=64#") == "sched.pack"
    assert span_name("bench.retire") == "bench.retire"
    names = {n for _, _, n in trace.sched_spans}
    assert names == {"sched.source", "sched.take_batch", "sched.pack",
                     "sched.dispatch", "sched.compile", "sched.fetch",
                     "sched.deliver"}


def test_what_trace_reports_is_unchanged(profile, trace):
    plain = Trace(profile)
    assert trace.spans == plain.spans
    assert trace.window == plain.window
    assert trace.idle_by_host_activity() == plain.idle_by_host_activity()
    assert trace.top_ops() == plain.top_ops()


@pytest.mark.parametrize("name, seconds, count", [
    ("sched.source", 2200 - 500 - 1000, 1),    # less generate and sleep
    ("sched.dispatch", 1600 - 1000, 1),        # less the compile
    ("sched.deliver", 1800 - 500 - 600, 1),    # less both retires
    ("sched.pack", 800, 1),                    # the one after the window
    ("sched.compile", 1000, 1),                # is left out
    ("bench.retire", 1100, 2),
    ("sched.missing", 0, 0),
])
def test_self_time(trace, name, seconds, count):
    got_s, got_n = trace.self_time_s(name)
    assert got_n == count
    assert got_s == pytest.approx(seconds * 1e-9)


def test_idle_is_put_down_to_the_innermost_span(trace):
    idle = trace.idle_by_span()
    # by hand: [0, 1000) is 200 in no span, 100 + 200 in sched.source and
    # 500 in bench.generate; [1500, 6000) crosses bench.sleep (700), the
    # rest of sched.source (200), take_batch (200), pack (800), dispatch
    # (100 + 500) around the compile (1000), fetch (200), deliver
    # (100 + 200) and a retire (500); [6500, 10000) has the other retire
    # (300), deliver (200) and 3000 in no span
    want = {UNATTRIBUTED: 3200, "sched.source": 500, "bench.generate": 500,
            "bench.sleep": 700, "sched.take_batch": 200, "sched.pack": 800,
            "sched.dispatch": 600, "sched.compile": 1000, "sched.fetch": 200,
            "sched.deliver": 500, "bench.retire": 800}
    assert set(idle) == set(want)
    for name, ns in want.items():
        assert idle[name] == pytest.approx(ns * 1e-9), name
    total = sum(e - s for s, e in trace.idle_gaps("/device:TPU:0")) * 1e-9
    assert sum(idle.values()) == pytest.approx(total)
    assert "bench.window" not in idle


def test_idle_with_no_spans_is_unattributed(trace):
    bare = SpanTrace.__new__(SpanTrace)
    bare.__dict__.update(trace.__dict__)
    bare.spans = [sp for sp in trace.spans if sp[2] == "bench.window"]
    bare.sched_spans = []
    assert bare.idle_by_span() == {UNATTRIBUTED: pytest.approx(9000e-9)}
