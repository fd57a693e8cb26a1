"""Host time per tick waiting for the device: the self time of
``engine.wait``, which blocks until a fused step's outputs are computed,
before the copies back to the host are timed."""

import host_spans


def read(w):
    return host_spans.ms_per_tick(w, ("engine.wait",))
