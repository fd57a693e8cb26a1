"""Share of the window's All-aboard attempts (paper section 9) that
waited out the section 9.2 timer for the last accept and fell back to
classic Paxos: 100 * all_aboard_timeouts / all_aboard_attempts, from
``ClusterEngine.telemetry()`` deltas.  A program without the counters,
or a window with no attempt, reads nothing."""


def read(w):
    t = w.telemetry
    if not t.get("all_aboard_attempts"):
        return None
    return 100.0 * t["all_aboard_timeouts"] / t["all_aboard_attempts"]
