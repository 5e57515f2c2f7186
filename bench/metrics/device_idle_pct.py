"""Share of the traced window in which no operation ran on the device,
percent (one minus the union of op intervals over the window)."""


def read(trace):
    busy = trace.busy_s()
    if busy <= 0.0:
        return None
    return 100.0 * (1.0 - busy / trace.window_s())
