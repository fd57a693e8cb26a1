"""Share of the ABD reads completed in the window that finished in one
round, without the section 11 write-back: 100 * (1 - write-backs /
reads), from the flight recorder's exact ``abd_read`` path counter and
the ``abd_read_write_backs`` count of ``ClusterEngine.telemetry()``.
A window with no read, or a program without the count, reads nothing."""


def read(w):
    reads = w.paths.get("abd_read", 0)
    if not reads or "abd_read_write_backs" not in w.telemetry:
        return None
    return 100.0 * (1.0 - w.telemetry["abd_read_write_backs"] / reads)
