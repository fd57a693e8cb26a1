"""Wall-clock host spans: where the host's time goes inside a served tick.

Everything else in :mod:`repro.obs` runs on virtual ticks, so dumps stay a
pure function of (seed, spec, mode).  :class:`HostClock` is the one
wall-clock part.  A :class:`~repro.obs.trace.FlightRecorder` owns one and
hands it to the cluster, the network, the fused engine (and its plane
stacks) and each machine's ingest scheduler; with no recorder attached
every span site sees ``None`` and pays an ``is not None`` branch.

Spans nest through a stack.  Per span name the clock accumulates integer
nanoseconds from ``time.perf_counter_ns``: ``total_ns``, ``self_ns`` (the
total less the child spans inside it) and a count ``n``.  Totals only
grow, so deltas over a window are never negative.  Each span is also a
``jax.profiler.TraceAnnotation("repro.<name>")``, which puts it on the
profiler's clock beside the device's events in any profiled slice.

:meth:`HostClock.totals` flattens the spans and counters into integer
keys (``span.<name>.self_ns``, ``span.tick.n``, ``ingest.wait_waves``);
``ClusterEngine.telemetry()`` reports them while a clock is attached, and
the recorder keeps the ``span.*`` keys out of its registry and dumps.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List


class HostClock:
    """Nested wall-clock spans and integer counters.

    ``begin``/``end`` open and close a span; ``switch`` closes the open
    span and opens a sibling at the same instant (one clock read for a
    run of sequential phases).  ``now`` is injectable for tests.
    """

    PREFIX = "repro."

    def __init__(self, now: Callable[[], int] = time.perf_counter_ns):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self._now = now
        self._stack: List[list] = []    # [name, start_ns, child_ns, annotation]
        self.spans: Dict[str, List[int]] = {}   # name -> [total, self, n]
        self.counters: Dict[str, int] = {}

    def begin(self, name: str, **meta) -> None:
        """Open span ``name``; ``meta`` goes on its profiler annotation."""
        ann = self._annotation(self.PREFIX + name, **meta)
        ann.__enter__()
        self._stack.append([name, self._now(), 0, ann])

    def switch(self, name: str) -> None:
        """Close the open span and open sibling ``name`` where it ended."""
        t = self._now()
        self._close(t)
        ann = self._annotation(self.PREFIX + name)
        ann.__enter__()
        self._stack.append([name, t, 0, ann])

    def end(self) -> None:
        """Close the innermost open span."""
        self._close(self._now())

    def _close(self, t: int) -> None:
        name, t0, child, ann = self._stack.pop()
        ann.__exit__(None, None, None)
        dur = t - t0
        acc = self.spans.get(name)
        if acc is None:
            acc = self.spans[name] = [0, 0, 0]
        acc[0] += dur
        acc[1] += dur - child
        acc[2] += 1
        if self._stack:
            self._stack[-1][2] += dur

    def count(self, name: str, n: int) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def totals(self) -> Dict[str, int]:
        """Every span's totals and every counter, as flat integer keys."""
        out: Dict[str, int] = {}
        for name, (total, self_ns, n) in self.spans.items():
            out[f"span.{name}.total_ns"] = total
            out[f"span.{name}.self_ns"] = self_ns
            out[f"span.{name}.n"] = n
        out.update(self.counters)
        return out
