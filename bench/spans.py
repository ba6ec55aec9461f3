"""The service's own host spans in a profiler trace, beside the benchmark's.

``SchedServer`` opens ``sched.*`` spans (``jax.profiler.TraceAnnotation``)
around the phases of its serve loop, its admissions and its compiles; the
benchmark opens ``bench.*`` spans around its own work.  Both lie on the
host plane on the device's clock, nested: the caller's ``bench.generate``
inside ``sched.source``, ``bench.retire`` inside ``sched.deliver``.

``SpanTrace`` is a ``bench.tracing.Trace`` that also loads the ``sched.*``
spans, with any ``#k=v#`` metadata suffix left off their names, and adds
two reductions; what ``Trace`` reports is left as it was:

* ``self_time_s(name)``: the summed time of the spans called ``name`` that
  started inside the window, less what spans nested in them cover, and
  their count;
* ``idle_by_span()``: every instant of device idle time inside the window
  put down to the innermost ``bench.*`` or ``sched.*`` span open at it
  (``bench.window`` left out), or to ``unattributed`` where none was open.
  The sweep is exact, where ``Trace.idle_by_host_activity`` labels each gap
  by its middle alone.
"""
from __future__ import annotations

from collections import defaultdict

from bench.tracing import HOST_PLANE, WINDOW_SPAN, Trace

SCHED_PREFIX = "sched."
UNATTRIBUTED = "unattributed"


def span_name(event_name):
    """``sched.pack#step=3,b=64#`` -> ``sched.pack``."""
    return event_name.split("#", 1)[0]


class SpanTrace(Trace):
    """A ``Trace`` with the service's ``sched.*`` host spans."""

    def __init__(self, profile):
        super().__init__(profile)
        self.sched_spans = []              # [(start, end, name)]
        for plane in profile.planes:
            if plane.name != HOST_PLANE:
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SCHED_PREFIX):
                        s = float(ev.start_ns)
                        self.sched_spans.append(
                            (s, s + float(ev.duration_ns), span_name(ev.name)))
        self._self_times = None

    def _nested(self):
        """Every ``bench.*`` (but the window) and ``sched.*`` span, outer
        spans before the spans they hold."""
        spans = [sp for sp in self.spans if sp[2] != WINDOW_SPAN]
        return sorted(spans + self.sched_spans, key=lambda x: (x[0], -x[1]))

    def self_time_s(self, name):
        """Summed self time (seconds) of the spans ``name`` started inside
        the window, and their count."""
        if self._self_times is None:
            lo, hi = self.window
            acc = defaultdict(lambda: [0.0, 0])
            spans = self._nested()
            covered = [0.0] * len(spans)
            stack = []
            for i, (s, e, _) in enumerate(spans):
                while stack and spans[stack[-1]][1] <= s:
                    stack.pop()
                if stack:
                    covered[stack[-1]] += min(e, spans[stack[-1]][1]) - s
                stack.append(i)
            for (s, e, n), c in zip(spans, covered):
                if lo <= s < hi:
                    acc[n][0] += e - s - c
                    acc[n][1] += 1
            self._self_times = acc
        total, count = self._self_times.get(name, (0.0, 0))
        return total * 1e-9, count

    def _innermost(self):
        """``(start, end, name)`` pieces of time, each under the innermost
        open span (``None`` where no span is open), in order."""
        pieces, stack, cur = [], [], None

        def close_until(t):
            nonlocal cur
            while stack and stack[-1][1] <= t:
                _, e, n = stack.pop()
                if e > cur:
                    pieces.append((cur, e, n))
                    cur = e

        for s, e, n in self._nested():
            if cur is None:
                cur = s
            close_until(s)
            if s > cur:
                pieces.append((cur, s, stack[-1][2] if stack else None))
                cur = s
            stack.append((s, e, n))
        if stack:
            close_until(float("inf"))
        return pieces

    def idle_by_span(self):
        """Idle seconds inside the window by the innermost open span,
        averaged over the chips, with ``unattributed`` for idle time in no
        span."""
        acc = defaultdict(float)
        pieces = [p for p in self._innermost() if p[2] is not None]
        for d in self.devices:
            gaps = self.idle_gaps(d)
            idle = sum(e - s for s, e in gaps)
            attributed, j = 0.0, 0
            for s, e, n in pieces:
                while j < len(gaps) and gaps[j][1] <= s:
                    j += 1
                k = j
                while k < len(gaps) and gaps[k][0] < e:
                    cut = min(e, gaps[k][1]) - max(s, gaps[k][0])
                    acc[n] += cut * 1e-9 / len(self.devices)
                    attributed += cut
                    k += 1
            acc[UNATTRIBUTED] += (idle - attributed) * 1e-9 / len(self.devices)
        return dict(acc)
