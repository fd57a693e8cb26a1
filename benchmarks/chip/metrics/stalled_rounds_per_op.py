"""Stalled rounds per op completed in the window: rounds that waited
``retransmit_threshold`` inspections without their quorum and were
retried with a higher TS (``round_timeouts``) or, for an ABD op,
retransmitted (``abd_retransmits``), from ``ClusterEngine.telemetry()``
deltas.  A program without the counters, or a window with no op, reads
nothing."""


def read(w):
    t = w.telemetry
    if "round_timeouts" not in t or not w.ops:
        return None
    return (t["round_timeouts"] + t["abd_retransmits"]) / w.ops
