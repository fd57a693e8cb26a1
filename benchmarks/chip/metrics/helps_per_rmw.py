"""Helps (paper sections 6 and 8: a proposer accepting another's value
at its own TS, after a wait on an accepted entry too) per RMW completed
in the window: helps / rmw_completed, from ``ClusterEngine.telemetry()``
deltas.  A program without the counters, or a window with no completed
RMW, reads nothing."""


def read(w):
    t = w.telemetry
    if not t.get("rmw_completed"):
        return None
    return t["helps"] / t["rmw_completed"]
