"""Model FLOP/s utilisation of federated LoRA rounds, in %: the model FLOPs
of a round (``bench/work/dsv2lite_fedlora.py``: 2x forward per trained
token plus the adapters' weight gradients, 1x forward per proxy token per
leave-one-out model, the held experts' work from the window's routed-token
counter; remat left out) times the rounds run in the traced window
(executions of ``jit__run_plain`` times the rounds each runs) over the
window, against the chip's peak."""


def read(obs, metric):
    trace, cfg, counters = obs["trace"], obs["cfg"], obs.get("counters")
    _, calls = trace.module_time_s("jit__run_plain")
    if not calls or trace.window_s <= 0 or not counters or not counters["rounds"]:
        return None
    routed = sum(counters["routed"]) / counters["rounds"]
    flops = obs["bench"].work("dsv2lite_fedlora").round_flops(cfg, routed)
    rate = calls * cfg["round"]["rounds_per_call"] / trace.window_s
    peak = obs["bench"].peaks(obs["device_kind"])["flops_per_s"]
    return 100.0 * flops * rate / peak
