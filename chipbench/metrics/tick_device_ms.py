"""Device time of one engine tick: the device's busy time in the traced
window (union of its operation intervals) over the ticks in the window, in
ms. Source: the device trace."""


def read(r, metric):
    ticks = r.counters.get("ticks")
    if r.trace is None or not ticks:
        return None
    return 1e3 * r.trace.busy_s / ticks
