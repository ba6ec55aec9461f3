"""How long requests waited in the service's queue: p99 over the requests
dispatched in the window of the time from when ``serve_stream`` pulled a
request from its source to when its step's executable call returned, in
ms, from the service's queue-wait histogram (``stats()``, the difference
of the two window marks; ``bench/counters.py``)."""
from bench.counters import window_percentile_ms


def read(obs, metric):
    return window_percentile_ms(obs, "queue_wait_counts", 99)
