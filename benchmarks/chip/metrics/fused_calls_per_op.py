"""Fused receiver plus issuer calls per op completed in the window, from
``ClusterEngine.telemetry()`` deltas."""


def read(w):
    t = w.telemetry
    if not w.ops:
        return None
    return (t["fused_receiver_calls"] + t["fused_issuer_calls"]) / w.ops
