"""The fused receiver step's share of the HBM roofline: the bytes of its
operands and results (from the shapes it receives), over its device time
in the trace, over the chip's peak HBM rate."""

import roofline

PROGRAM = "_fused_receiver_step"


def read(w):
    if w.trace is None or PROGRAM not in w.call_bytes:
        return None
    n, device_s = w.trace.module(PROGRAM)
    if not n or device_s <= 0:
        return None
    return roofline.share(n, w.call_bytes[PROGRAM], device_s,
                          w.peaks["hbm_bytes_per_s"])
