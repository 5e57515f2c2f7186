"""Time per epoch to upload the epoch's ids, milliseconds: from the
start of the runtime's ``id_upload`` span (its ``jax.device_put``) to the
TPU runtime seeing the copy of that many bytes done, averaged over the
window's epochs.  ``device_put`` returns before the copy ends, so the
span alone does not hold it."""


def read(trace):
    lags = trace.upload_lags_s()
    return sum(lags) / len(lags) * 1e3 if lags else None
