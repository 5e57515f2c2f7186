"""Device time per epoch of the ``runtime._epoch_step`` program (decide,
migrate and account for every lane; ``jit__epoch_step``), milliseconds."""


def read(trace):
    s = trace.module_s("jit__epoch_step")
    return s / trace.n_epochs * 1e3 if s > 0 else None
