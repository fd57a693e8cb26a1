"""Host time per tick in the machines' scalar decisions: the self time of
the ``machine`` spans, one per resumption of a machine's tick generator
in ``ClusterEngine.drive`` (less the plane pulls of bridge checkouts)."""

import host_spans


def read(w):
    return host_spans.ms_per_tick(w, ("machine",))
