"""Wall time of one cluster step (one simulated tick), on the host clock,
over the window less its profiled slice."""


def read(w):
    if not w.host_ticks:
        return None
    return w.host_s / w.host_ticks * 1e3
