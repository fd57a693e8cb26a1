"""Share of completed RMWs that took the All-aboard fast path (paper
section 9) rather than falling back to classic Paxos, from the flight
recorder's exact path counters over the window."""


def read(w):
    fast = w.paths.get("all_aboard_fast", 0)
    slow = w.paths.get("cp_slow", 0)
    if fast + slow == 0:
        return None
    return 100.0 * fast / (fast + slow)
