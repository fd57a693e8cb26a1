"""From a profiler trace to device busy time, idle gaps and call times.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (with
nothing but JAX) into plain event lists; ``reduce`` turns those lists into
a :class:`Reduction`.  The two are apart so that the arithmetic can be
checked on a small recorded trace (``tests/``) without a chip.

Event lists are ``{"device": [[line, name, start_ns, dur_ns], ...],
"host": [[name, start_ns, dur_ns], ...]}``: the device events of the first
TPU (this benchmark's cells run on one), and the host spans the benchmark
placed (names starting with ``bench.``).  Both are on the profiler's one
clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN = "bench."
SLICE = SPAN + "slice"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def extract(trace_dir: str) -> dict:
    """Event lists from the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    device, host = [], []
    devices = sorted(p.name for p in pd.planes
                     if p.name.startswith("/device:TPU:"))
    for plane in pd.planes:
        if devices and plane.name == devices[0]:
            for line in plane.lines:
                for e in line.events:
                    device.append([line.name, _op_name(e.name),
                                   float(e.start_ns), float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"device": device, "host": host}


@dataclasses.dataclass
class Reduction:
    window_s: float                       # the profiled slice
    busy_s: float                         # union of device op intervals
    modules: Dict[str, Tuple[int, float]]  # program -> (runs, device s)
    device_ops: List[Tuple[str, float]]   # op name -> device s, top 10
    idle_gaps: List[Tuple[str, float]]    # host span during a gap, top 10

    def module(self, fragment: str) -> Tuple[int, float]:
        """Runs and device seconds of the programs whose name holds
        ``fragment`` (a jitted function's name)."""
        n = t = 0
        for name, (k, s) in self.modules.items():
            if fragment in name:
                n += k
                t += s
        return n, t


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _program(name: str) -> str:
    """``jit__fused_receiver_step(123)`` -> ``jit__fused_receiver_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction:
    ``%fusion.95 = (s32[...]) fusion(...)`` -> ``fusion.95``."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce(events: dict) -> Optional[Reduction]:
    """None when the trace holds no slice span or no device op in it.
    A device plane without its ops line is a layout this reduction does
    not know: an error, not an idle device."""
    spans = [e for e in events["host"] if e[0] == SLICE]
    if not spans:
        return None
    s0, dur = spans[0][1], spans[0][2]
    s1 = s0 + dur
    ops = [e for e in events["device"] if e[0] == OPS_LINE]
    if events["device"] and not ops:
        lines = sorted({e[0] for e in events["device"]})
        raise ValueError(f"device plane has no {OPS_LINE!r} line: {lines}")
    clipped = [(max(a, s0), min(a + d, s1)) for _l, _n, a, d in ops
               if a < s1 and a + d > s0]
    busy = _union(clipped)
    if not busy:
        return None
    per_op: Dict[str, float] = defaultdict(float)
    for _l, name, a, d in ops:
        lo, hi = max(a, s0), min(a + d, s1)
        if hi > lo:
            per_op[name] += (hi - lo) / 1e9
    modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for line, name, a, d in events["device"]:
        if line == MODULES_LINE and s0 <= a < s1:
            m = modules[_program(name)]
            m[0] += 1
            m[1] += d / 1e9
    gaps = []
    edges = [s0] + [x for iv in busy for x in iv] + [s1]
    host = [e for e in events["host"] if e[0] != SLICE]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        inside = [h for h in host if h[1] <= mid < h[1] + h[2]]
        label = (min(inside, key=lambda h: h[2])[0][len(SPAN):]
                 if inside else "outside_spans")
        gaps.append((label, (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return Reduction(window_s=dur / 1e9,
                     busy_s=sum(b - a for a, b in busy) / 1e9,
                     modules={k: (int(v[0]), v[1])
                              for k, v in modules.items()},
                     device_ops=top, idle_gaps=gaps[:10])
