"""Device: 1 - (union of the intervals in which an operation ran) / traced
window, averaged over the chips, in per cent."""


def read(r):
    if r.trace is None or not r.trace.devices or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
