"""Host time per epoch in the hint pipeline, milliseconds: the providers
(the harness's ``hint_ranks`` span around ``HintPipeline.epoch_ranks``)
plus the hand-over of their ranks (``hint_set``, around
``EpochRuntime.set_hint_ranks``, whose upload is the runtime's own
``hint_refresh`` span)."""


def read(trace):
    parts = [trace.span_s(name) for name in ("hint_ranks", "hint_set")]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts) / trace.n_epochs * 1e3
