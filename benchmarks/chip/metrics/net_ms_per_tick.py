"""Host time per tick in the simulated network: the self time of the
``net.deliver`` span (``Network.deliver_due``) and of ``net.send`` (the
buffered-send flush at the end of ``ClusterEngine.step_all``)."""

import host_spans


def read(w):
    return host_spans.ms_per_tick(w, ("net.deliver", "net.send"))
