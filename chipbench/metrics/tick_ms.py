"""Host time of one engine tick: the mean length of the benchmark's ``step``
spans (around ``ServingEngine.step``) in the window, in ms. Source: the
host clock."""


def read(r, metric):
    lo, hi = r.window
    xs = [e - s for n, s, e in r.spans if n == "step" and s >= lo and e <= hi]
    return 1e3 * sum(xs) / len(xs) if xs else None
