"""Reduce a JAX profiler trace to device busy time, op and program times.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  On a TPU each chip is a plane named
``/device:TPU:<i>``; its line ``XLA Ops`` holds one event per operation
that ran (a Pallas kernel is one such operation, named after its
``pallas_call``), and its line ``XLA Modules`` one event per execution of
a compiled program (``jit_<function>(<fingerprint>)``).  The host plane
``/host:CPU`` holds the benchmark's own ``TraceAnnotation`` spans,
``bench.*``, on the same clock: ``bench.window`` marks the measured window.

Busy time is the union of the op intervals inside the window; the idle
share is one minus busy over the window.  Control-flow ops that hold
others (a scan's ``while``, ``conditional``, ``call``) are left out of
every reduction: they span their body, and the ops of the body count
instead, so that a gap inside a scan shows as idle.  Each idle gap is put down to
the benchmark span the host was in at the gap's middle, or to
``program`` (the system under test's own host code) when it was in none.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINER_OPS = ("while", "conditional", "call")

_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name):
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def module_name(event_name):
    """``jit_serve_step(3866365110627983181)`` -> ``jit_serve_step``."""
    return event_name.split("(", 1)[0]


def merge(intervals):
    """Sorted, non-overlapping union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


class Trace:
    """Device ops and programs per chip, and the benchmark's host spans.

    Times are nanoseconds on the profiler's clock.  ``window`` is the
    ``bench.window`` span, or the extent of the device ops where the trace
    holds none.
    """

    def __init__(self, profile):
        self.ops = defaultdict(list)       # device -> [(start, end, name)]
        self.modules = defaultdict(list)   # device -> [(start, end, name)]
        self.spans = []                    # [(start, end, name)]
        for plane in profile.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        dest, rename = self.ops[plane.name], op_name
                    elif line.name == MODULES_LINE:
                        dest, rename = self.modules[plane.name], module_name
                    else:
                        continue
                    for ev in line.events:
                        s = float(ev.start_ns)
                        dest.append((s, s + float(ev.duration_ns),
                                     rename(ev.name)))
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            s = float(ev.start_ns)
                            self.spans.append((s, s + float(ev.duration_ns),
                                               ev.name))
        win = [(s, e) for s, e, n in self.spans if n == WINDOW_SPAN]
        if win:
            self.window = win[0]
        else:
            ends = [(s, e) for ops in self.ops.values() for s, e, _ in ops]
            self.window = ((min(s for s, _ in ends), max(e for _, e in ends))
                           if ends else (0.0, 0.0))

    @classmethod
    def from_file(cls, path):
        from jax.profiler import ProfileData
        return cls(ProfileData.from_file(path))

    @property
    def devices(self):
        return sorted(self.ops)

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) * 1e-9

    def _in_window(self, events):
        lo, hi = self.window
        return [ev for ev in events if lo <= ev[0] < hi]

    # ------------------------------------------------------------- device
    def _busy(self, device):
        """Merged intervals of the device's leaf ops, clipped to the
        window."""
        lo, hi = self.window
        return merge(_clip([(s, e) for s, e, n in self.ops[device]
                            if n not in CONTAINER_OPS], lo, hi))

    def busy_s(self, device):
        return sum(e - s for s, e in self._busy(device)) * 1e-9

    def mean_busy_s(self):
        """Busy seconds averaged over the chips in the trace."""
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def op_time_s(self, contains):
        """Summed device time of the ops whose name holds ``contains``,
        started inside the window, over all chips; and their count."""
        total, count = 0.0, 0
        for d in self.devices:
            for s, e, n in self._in_window(self.ops[d]):
                if contains in n:
                    total += e - s
                    count += 1
        return total * 1e-9, count

    def module_time_s(self, name):
        """Summed device time and count of the executions of the program
        ``name`` (e.g. ``jit_serve_step``) started inside the window."""
        total, count = 0.0, 0
        for d in self.devices:
            for s, e, n in self._in_window(self.modules[d]):
                if n == name:
                    total += e - s
                    count += 1
        return total * 1e-9, count

    def top_ops(self, k=10):
        """The ops that took most device time in the window, summed by
        name over the chips (control-flow ops left out)."""
        acc = defaultdict(float)
        for d in self.devices:
            for s, e, n in self._in_window(self.ops[d]):
                if n not in CONTAINER_OPS:
                    acc[n] += (e - s) * 1e-9
        return sorted(([n, t] for n, t in acc.items()), key=lambda x: -x[1])[:k]

    def idle_gaps(self, device):
        lo, hi = self.window
        gaps, cur = [], lo
        for s, e in self._busy(device):
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            gaps.append((cur, hi))
        return gaps

    def idle_by_host_activity(self, k=10):
        """Idle seconds inside the window, summed by what the host was
        doing at each gap's middle (averaged over the chips)."""
        acc = defaultdict(float)
        spans = sorted(self.spans)
        starts = [s for s, _, _ in spans]
        for d in self.devices:
            for s, e in self.idle_gaps(d):
                mid = 0.5 * (s + e)
                label = "program"
                i = bisect.bisect_right(starts, mid)
                # scan back over spans that started before mid; the latest
                # one still open is the innermost
                for j in range(i - 1, max(i - 64, -1), -1):
                    ss, se, sn = spans[j]
                    if sn != WINDOW_SPAN and ss <= mid < se:
                        label = sn
                        break
                acc[label] += (e - s) * 1e-9 / len(self.devices)
        return sorted(([n, t] for n, t in acc.items()), key=lambda x: -x[1])[:k]
