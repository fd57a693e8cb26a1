"""Share of the window's waves that ran both halves, a fused receiver
call and a fused issuer call, in one host round trip each way:
100 * paired_waves / waves, where waves = receiver calls + issuer calls
- paired_waves counts each wave with a call once, from
``ClusterEngine.telemetry()`` deltas.  A program without the counter,
or a window with no call, reads nothing."""


def read(w):
    t = w.telemetry
    if "paired_waves" not in t:
        return None
    waves = (t["fused_receiver_calls"] + t["fused_issuer_calls"]
             - t["paired_waves"])
    if not waves:
        return None
    return 100.0 * t["paired_waves"] / waves
