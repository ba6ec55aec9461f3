"""The scheduling service's latency histograms over the benchmark's window.

``SchedServer.stats()`` reports cumulative histograms (``latency_edges_s``
and one list of counts per histogram, such as ``queue_wait_counts``);
``bench/loadgen.py`` snapshots ``stats()`` at the window's start and end.
The window's histogram is the difference of the two, and a percentile is
read from it by linear interpolation inside its bucket: bucket 0 spans
[0, edges[0]), bucket i [edges[i-1], edges[i]), and the open last bucket
gives its edge.  Where ``stats()`` has no such histogram, as in a service
without one, there is nothing to read.
"""
import numpy as np


def window_histogram(obs, key):
    """``(edges, counts)`` of the histogram ``key`` over the window, or
    ``None`` where the marks lack it."""
    start, end = obs["counters"].get("start"), obs["counters"].get("end")
    if not start or not end or key not in start or key not in end:
        return None
    return (np.asarray(end["latency_edges_s"], np.float64),
            np.subtract(end[key], start[key]).astype(np.float64))


def percentile(edges, counts, q):
    """The ``q``-th percentile of the histogram, in seconds, or ``None``
    for an empty one."""
    total = counts.sum()
    if total <= 0:
        return None
    cum = np.cumsum(counts)
    rank = q / 100.0 * total
    i = max(int(np.searchsorted(cum, rank, side="left")),
            int(np.argmax(counts > 0)))
    if i >= edges.size:
        return float(edges[-1])
    lo = edges[i - 1] if i else 0.0
    return float(lo + (edges[i] - lo) * (rank - (cum[i] - counts[i])) / counts[i])


def window_percentile_ms(obs, key, q):
    """The ``q``-th percentile of histogram ``key`` over the window, in ms."""
    got = window_histogram(obs, key)
    value = percentile(*got, q) if got is not None else None
    return value * 1e3 if value is not None else None
