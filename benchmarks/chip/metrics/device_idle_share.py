"""Share of the profiled slice in which no operation ran on the device:
1 minus the union of device op intervals over the slice."""


def read(w):
    if w.trace is None or w.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - w.trace.busy_s / w.trace.window_s)
