"""Device time of the ``client_gather`` kernel per FL round, in us: the
summed duration of its calls (the scheduled clients' examples and their
labels) in the trace's window over the rounds that the window's
``SparseAsyncFLTrainer.run`` programs (``jit__run_plain``) ran.  None
where the kernel did not run: the round gathered with XLA instead."""


def read(obs, metric):
    kernel_s, calls = obs["trace"].op_time_s("client_gather")
    _, programs = obs["trace"].module_time_s("jit__run_plain")
    if not calls or not programs:
        return None
    return kernel_s / (programs * obs["cfg"]["round"]["rounds_per_call"]) * 1e6
