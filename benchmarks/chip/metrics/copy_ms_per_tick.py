"""Host time per tick copying between host and device: the self time of
``engine.upload`` (staging buffer to the device), ``engine.download``
(replies, mask and actions back), ``plane.push`` and ``plane.pull``
(whole plane stacks, wherever they move)."""

import host_spans


def read(w):
    return host_spans.ms_per_tick(w, ("engine.upload", "engine.download",
                                      "plane.push", "plane.pull"))
