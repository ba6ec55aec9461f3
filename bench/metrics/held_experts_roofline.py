"""The held experts' grouped matmuls against their roofline, in %: the least
time the chip needs for the window's work (``bench/work/dsv2lite_fedlora.py``
``held_experts_work``, from the widths and the routed-token counter),
max(flops / peak flops, bytes / peak bandwidth), over the summed device
time of the ``ragged-dot`` kernels (and their group metadata) in the
window.  Rematerialised and recomputed calls count in the time, not in
the work."""


def read(obs, metric):
    kernel_s, calls = obs["trace"].op_time_s("ragged-dot")
    counters = obs.get("counters")
    if not calls or not counters or not counters["rounds"]:
        return None
    cfg = obs["cfg"]
    routed = sum(counters["routed"]) / counters["rounds"]
    flops, bytes_ = obs["bench"].work("dsv2lite_fedlora").held_experts_work(
        cfg, routed)
    peaks = obs["bench"].peaks(obs["device_kind"])
    least = max(flops / peaks["flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least * counters["rounds"] / kernel_s
