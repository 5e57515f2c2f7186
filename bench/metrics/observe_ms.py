"""Device time per epoch of the ``telemetry.observe_all`` program (its
``jit_observe_all`` executions in the trace), milliseconds."""


def read(trace):
    s = trace.module_s("jit_observe_all")
    return s / trace.n_epochs * 1e3 if s > 0 else None
