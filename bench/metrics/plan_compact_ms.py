"""Device time per epoch of ``placement.apply_plan``'s free-slot
compaction (scope ``placement.free_slots`` inside ``jit__epoch_step``),
milliseconds."""


def read(trace):
    s = trace.scope_s("placement.free_slots", "jit__epoch_step")
    return s / trace.n_epochs * 1e3 if s else None
