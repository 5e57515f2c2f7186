"""Time from the device's last op to the records on the host, per record
pull, milliseconds: each ``record_sync`` span's end minus the end of the
last device op that ended before it, averaged over the window's pulls
(one per epoch at ``sync_every=1``)."""
import trace_scopes


def read(trace):
    t = trace_scopes.of(trace)
    lags = [] if t is None else t.record_lags_s()
    return sum(lags) / len(lags) * 1e3 if lags else None
