"""Waves a message waited in the ingest scheduler behind a conflicting
head: within one drain the i-th batch waited i waves, so the program's
counter ``ingest.wait_waves`` (sum of i times the batch's messages) over
``ingest.emitted``, from ``ClusterEngine.telemetry()`` deltas."""


def read(w):
    emitted = w.telemetry.get("ingest.emitted", 0)
    if not emitted:
        return None
    return w.telemetry["ingest.wait_waves"] / emitted
