"""Host time per epoch in the lookahead window's rank (the runtime's
``hints.lookahead`` span inside ``HintPipeline.epoch_ranks``),
milliseconds."""


def read(trace):
    s = trace.span_s("hints.lookahead")
    return s / trace.n_epochs * 1e3 if s else None
