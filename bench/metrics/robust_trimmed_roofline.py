"""``robust_trimmed`` against its roofline, in %: the least time the chip needs
for the algorithm's work on the round's (M, P) update matrix
(``bench/work/robust_trimmed.py``, once a round), max(flops / peak flops,
bytes / peak bandwidth), over the kernel's summed device time in the
window.  The bytes bound applies at the trainer's shapes."""


def read(obs, metric):
    kernel_s, calls = obs["trace"].op_time_s("robust_trimmed")
    if not calls:
        return None
    cfg = obs["cfg"]
    flops, bytes_ = obs["bench"].work("robust_trimmed").work(cfg["round"]["n_sched"],
                                                   cfg["model"]["params"])
    peaks = obs["bench"].peaks(obs["device_kind"])
    least = max(flops / peaks["flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least * calls / kernel_s
