"""Device time per epoch of ``placement.apply_plan``'s free-slot
compaction (scope ``placement.free_slots`` inside ``jit__epoch_step``),
milliseconds."""
import trace_scopes


def read(trace):
    t = trace_scopes.of(trace)
    return None if t is None else trace_scopes.per_epoch_ms(
        t.scope_s("placement.free_slots", "jit__epoch_step"), t)
