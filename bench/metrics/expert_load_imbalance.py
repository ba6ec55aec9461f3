"""Load imbalance of the held experts over the window: the largest held
expert's routed assignments over the mean held expert's (1 is even), from
the program's counter (the round metrics' ``moe_routed``, summed over the
window's rounds)."""


def read(obs, metric):
    counters = obs.get("counters")
    if not counters or not sum(counters["routed"]):
        return None
    routed = counters["routed"]
    return max(routed) / (sum(routed) / len(routed))
