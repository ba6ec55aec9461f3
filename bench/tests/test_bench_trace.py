"""The trace reduction on a small synthetic trace (CPU only)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.tracing import Trace, merge, module_name, op_name  # noqa: E402


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
def trace():
    from jax.profiler import ProfileData
    device = _events({
        "XLA Modules": [("jit_serve_step(123)", 1000, 400),
                        ("jit_serve_step(123)", 2000, 600),
                        ("jit_admit(9)", 3000, 100)],
        "XLA Ops": [("%fusion.1 = f32[2] fusion()", 1000, 100),
                    ("%vmap_jit_glr_step_tenants__.1 = f32[2] custom-call()", 1100, 300),
                    ("%copy.7 = f32[2] copy()", 1150, 100),   # overlaps
                    ("%vmap_jit_glr_step_tenants__.1 = f32[2] custom-call()", 2000, 600),
                    ("%while.4 = (f32[2]) while()", 2000, 600),   # holds ops
                    ("%while.5 = (f32[2]) while()", 2600, 400),   # holds none
                    ("%fusion.2 = f32[2] fusion()", 3000, 100),
                    ("%fusion.3 = f32[2] fusion()", 6000, 100)],   # after window
    })
    host = _events({
        "main": [("bench.window", 500, 4500),
                 ("bench.generate", 1500, 300),
                 ("bench.sleep", 3200, 1000)],
    })
    text = (f'planes {{ id: 1 name: "/device:TPU:0" {device} }} '
            f'planes {{ id: 2 name: "/host:CPU" {host} }}')
    return Trace(ProfileData.from_text_proto(text))


def test_names_are_normalised():
    assert op_name("%fusion.12 = f32[8] fusion(x)") == "fusion"
    assert op_name("%vmap_jit_glr_step_tenants__.1 = (f32[1]) custom-call()") \
        == "vmap_jit_glr_step_tenants__"
    assert module_name("jit_serve_step(3866365110627983181)") == "jit_serve_step"


def test_merge_unions_overlaps():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_window_busy_and_idle(trace):
    assert trace.window == (500.0, 5000.0)
    assert trace.window_s == pytest.approx(4.5e-6)
    # leaf ops inside the window: [1000, 1400] (three overlapping),
    # [2000, 2600], [3000, 3100]; the op at 6000 lies outside, and the
    # control-flow ops are no busy time of their own: the gap [2600, 3000]
    # under ``while.5`` is idle
    assert trace.mean_busy_s() == pytest.approx((400 + 600 + 100) * 1e-9)
    gaps = trace.idle_gaps("/device:TPU:0")
    assert gaps == [(500.0, 1000.0), (1400.0, 2000.0), (2600.0, 3000.0),
                    (3100.0, 5000.0)]


def test_op_and_program_times(trace):
    assert trace.op_time_s("glr_step") == (pytest.approx(900e-9), 2)
    assert trace.module_time_s("jit_serve_step") == (pytest.approx(1000e-9), 2)
    assert trace.top_ops(2)[0] == ["vmap_jit_glr_step_tenants__",
                                   pytest.approx(900e-9)]
    assert "while" not in [n for n, _ in trace.top_ops()]


def test_idle_is_put_down_to_the_host_span(trace):
    idle = dict(trace.idle_by_host_activity())
    # gap middles: 750 and 2300 (no span: the program), 1700 (generate),
    # 4050 (sleep)
    assert idle["bench.generate"] == pytest.approx(600e-9)
    assert idle["bench.sleep"] == pytest.approx(1900e-9)
    assert idle["program"] == pytest.approx((500 + 400) * 1e-9)
