"""Device time of one FL round, in ms: the summed duration of the
``SparseAsyncFLTrainer.run`` programs (``jit__run_plain``) in the trace's
window over the rounds they ran."""


def read(obs, metric):
    total, count = obs["trace"].module_time_s("jit__run_plain")
    per_call = obs["cfg"]["round"]["rounds_per_call"]
    return total / (count * per_call) * 1e3 if count else None
