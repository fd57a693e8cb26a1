"""Host time per tick launching the fused steps: the self time of
``engine.launch``, the call of the jitted ``_fused_*_step`` until it
returns."""

import host_spans


def read(w):
    return host_spans.ms_per_tick(w, ("engine.launch",))
