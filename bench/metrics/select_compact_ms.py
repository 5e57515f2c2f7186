"""Device time per epoch of the select's compaction (the ``searchsorted``
over the selection's prefix count, scope ``selectk.compact`` inside
``jit__epoch_step``), milliseconds."""
import trace_scopes


def read(trace):
    t = trace_scopes.of(trace)
    return None if t is None else trace_scopes.per_epoch_ms(
        t.scope_s("selectk.compact", "jit__epoch_step"), t)
