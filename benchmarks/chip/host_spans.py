"""Host time per cluster tick from the program's own wall-clock spans.

While a flight recorder is attached (every traced run), the program's
``repro.obs.HostClock`` reports through ``ClusterEngine.telemetry()``
integer totals per span: ``span.<name>.self_ns`` (the span's time less
its child spans) and ``span.<name>.n``, with one ``tick`` span per
cluster step.  Summing self times counts no nanosecond twice.  A program
without the clock has no such keys, and the readers return None.
"""


def ms_per_tick(w, spans):
    """Self time of ``spans`` over the window, in ms per ``tick`` span."""
    t = w.telemetry
    ticks = t.get("span.tick.n", 0)
    if not ticks:
        return None
    return sum(t.get(f"span.{s}.self_ns", 0) for s in spans) / ticks / 1e6
