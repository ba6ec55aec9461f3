"""Live requests per dispatched row of the serve loop over the window, in
%, from ``SchedServer.stats()`` (``served`` / ``rows_dispatched``)."""


def read(obs, metric):
    start, end = obs["counters"].get("start"), obs["counters"].get("end")
    if not start or not end:
        return None
    rows = end["rows_dispatched"] - start["rows_dispatched"]
    if rows <= 0:
        return None
    return 100.0 * (end["served"] - start["served"]) / rows
