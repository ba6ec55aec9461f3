"""Device time of one execution of the compiled serve step, in us: the
summed duration of the ``jit_serve_step`` programs in the trace's window
over their count."""


def read(obs, metric):
    total, count = obs["trace"].module_time_s("jit_serve_step")
    return total / count * 1e6 if count else None
