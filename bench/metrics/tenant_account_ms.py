"""Device time per epoch of the fleet's per-tenant accounting (scope
``tenancy.account`` inside ``jit__epoch_step``: the (lanes, tenants) sums
of the record fields), milliseconds."""


def read(trace):
    s = trace.scope_s("tenancy.account", "jit__epoch_step")
    return s / trace.n_epochs * 1e3 if s else None
