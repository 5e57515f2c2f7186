"""Host time per epoch in the phase detector's update (the runtime's
``hints.detector`` span inside ``HintPipeline.epoch_ranks``),
milliseconds."""


def read(trace):
    s = trace.span_s("hints.detector")
    return s / trace.n_epochs * 1e3 if s else None
