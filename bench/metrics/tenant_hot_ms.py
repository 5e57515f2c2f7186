"""Device time per epoch of the fleet's hot sets (scope ``tenancy.hot_set``
inside ``jit__epoch_step``: the epoch's exact top ``k_hot`` of the ground
truth and each tenant's top ``hot_k[t]`` of its own slice), milliseconds."""


def read(trace):
    s = trace.scope_s("tenancy.hot_set", "jit__epoch_step")
    return s / trace.n_epochs * 1e3 if s else None
