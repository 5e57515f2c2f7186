"""Device time per epoch of the select's compaction (scope
``selectk.compact`` inside ``jit__epoch_step``), milliseconds."""


def read(trace):
    s = trace.scope_s("selectk.compact", "jit__epoch_step")
    return s / trace.n_epochs * 1e3 if s else None
