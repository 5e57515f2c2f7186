"""Device time per epoch of the Mosaic select kernel (``hist_select``, the
custom calls inside ``jit__epoch_step``), milliseconds."""


def read(trace):
    s = trace.kernel_s("jit__epoch_step")
    return s / trace.n_epochs * 1e3 if s > 0 else None
