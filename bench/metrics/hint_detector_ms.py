"""Host time per epoch in the phase detector's update (the runtime's
``hints.detector`` span inside ``HintPipeline.epoch_ranks``),
milliseconds."""
import trace_scopes


def read(trace):
    t = trace_scopes.of(trace)
    return None if t is None else trace_scopes.per_epoch_ms(
        t.span_s("hints.detector"), t)
