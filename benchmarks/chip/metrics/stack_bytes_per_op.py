"""Whole-stack bytes (the KV and issuer-table stacks, in a wave's upload
and download or out of one) moved between host and device per op
completed in the window, from ``ClusterEngine.telemetry()`` deltas.
The rest of ``hd_bytes_per_op`` is the per-wave staging and replies.
A program without the stack counters reads nothing."""


def read(w):
    t = w.telemetry
    if not w.ops or "stack_h2d_bytes" not in t:
        return None
    return (t["stack_h2d_bytes"] + t["stack_d2h_bytes"]) / w.ops
