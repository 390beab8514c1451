"""Device idle share of the traced window: 1 − (union of the device's
operation intervals ÷ the window), averaged over the chips the cell uses.
Source: the device trace. Unit: %."""


def read(r, metric):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
