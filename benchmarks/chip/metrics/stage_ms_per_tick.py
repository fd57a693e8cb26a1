"""Host time per tick staging the fused calls: the self time of
``engine.stage`` (lanes of each message or reply, the ``is_registered``
gather, the staging scatter) and ``engine.unstage`` (the registry
scatter, result row views, the reset of the staging buffer)."""

import host_spans


def read(w):
    return host_spans.ms_per_tick(w, ("engine.stage", "engine.unstage"))
