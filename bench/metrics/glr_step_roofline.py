"""``glr_step`` (the GLR detector kernel, tenant grid) against its
roofline, in %: the least time the chip needs for the algorithm's work,
max(flops / peak flops, bytes / peak bandwidth) from
``bench/work/glr_step.py``, over the kernel's summed device time in the
window.  The bytes bound applies at the service's shapes.  One call per
serve step; the calls' row counts come from the server's per-size step
counts over the same window."""


def read(obs, metric):
    kernel_s, calls = obs["trace"].op_time_s("glr_step")
    start, end = obs["counters"].get("start"), obs["counters"].get("end")
    if not calls or not start or not end:
        return None
    s = obs["cfg"]["scheduler"]
    work = obs["bench"].work("glr_step").work
    peaks = obs["bench"].peaks(obs["device_kind"])
    flops = bytes_ = 0
    for rows, n_end in end["sizes_used"].items():
        steps = n_end - start["sizes_used"].get(rows, 0)
        f, b = work(int(rows), s["n_channels"], s["history"])
        flops += steps * f
        bytes_ += steps * b
    least = max(flops / peaks["flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
