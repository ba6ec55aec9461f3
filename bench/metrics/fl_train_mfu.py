"""Model FLOP/s utilisation of the FL rounds, in %: the model FLOPs of a
round (``bench/work/fedavg_cnn.py``: 3x forward per trained image, 1x per
proxy image) times the rounds run in the traced window (executions of
``jit__run_plain`` times the rounds each runs) over the window, against
the chip's peak."""


def read(obs, metric):
    trace, cfg = obs["trace"], obs["cfg"]
    _, calls = trace.module_time_s("jit__run_plain")
    if not calls or trace.window_s <= 0:
        return None
    flops = obs["bench"].work("fedavg_cnn").round_flops(cfg)
    rate = calls * cfg["round"]["rounds_per_call"] / trace.window_s
    peak = obs["bench"].peaks(obs["device_kind"])["flops_per_s"]
    return 100.0 * flops * rate / peak
