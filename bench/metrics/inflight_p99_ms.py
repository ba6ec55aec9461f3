"""How long dispatched requests stayed in flight: p99 over the requests
whose step was fetched in the window of the time from when their step's
executable call returned to when its answers were in host memory, in ms,
from the service's in-flight histogram (``stats()``, the difference of the
two window marks; ``bench/counters.py``)."""
from bench.counters import window_percentile_ms


def read(obs, metric):
    return window_percentile_ms(obs, "inflight_counts", 99)
