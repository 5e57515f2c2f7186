"""Time from the device's last op to the records on the host, per record
pull, milliseconds: each ``record_sync`` span's end minus the end of the
last device op that ended before it, averaged over the window's pulls
(one per epoch at ``sync_every=1``)."""


def read(trace):
    lags = trace.record_lags_s()
    return sum(lags) / len(lags) * 1e3 if lags else None
