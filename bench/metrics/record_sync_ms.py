"""Host time per epoch in the runtime's ``record_sync`` span (the pull of
the record buffer, mostly waiting for the device), milliseconds."""


def read(trace):
    s = trace.span_s("record_sync")
    return None if s is None else s / trace.n_epochs * 1e3
