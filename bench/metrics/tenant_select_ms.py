"""Device time per epoch of the fleet's segment-capped select (scope
``tenancy.select`` inside ``jit__epoch_step``: each tenant's top
``caps[t]`` of every key row and the masking of the rest), milliseconds."""


def read(trace):
    s = trace.scope_s("tenancy.select", "jit__epoch_step")
    return s / trace.n_epochs * 1e3 if s else None
