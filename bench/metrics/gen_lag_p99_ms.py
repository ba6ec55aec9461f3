"""How late the load generator sent requests: p99 over the window's
requests of (sent - due), in ms, on the host clock.  A starved generator
shows here, and not as a slow service."""
import numpy as np


def read(obs, metric):
    lag = np.asarray(obs["loop"].gen_lag)
    return float(np.percentile(lag, 99)) * 1e3 if lag.size else None
