"""Device time in the program's Pallas (Mosaic) kernels per step or per
tick: the self time of every ``tpu_custom_call`` operation in the traced
window over the steps (training) or ticks (serving) in it, in ms. The
kernels carry no names, so this is their sum, not one kernel's time.
Source: the device trace."""


def read(r, metric):
    n = r.counters.get("steps") or r.counters.get("ticks")
    if r.trace is None or not n:
        return None
    return 1e3 * r.trace.custom_call_seconds() / n
