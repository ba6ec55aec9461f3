"""Share of the traced window in which no operation ran on the device, in
%: 1 - (union of the device's op intervals) / window, averaged over the
chips."""


def read(obs, metric):
    trace = obs["trace"]
    if trace.window_s <= 0 or not trace.devices:
        return None
    return 100.0 * (1.0 - trace.mean_busy_s() / trace.window_s)
