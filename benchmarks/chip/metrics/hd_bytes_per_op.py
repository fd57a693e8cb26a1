"""Bytes moved between host and device per op completed in the window
(plane syncs, pulls, staging and replies), from
``ClusterEngine.telemetry()`` deltas."""


def read(w):
    if not w.ops:
        return None
    return (w.telemetry["h2d_bytes"] + w.telemetry["d2h_bytes"]) / w.ops
