"""Protocol flight recorder: unified metrics, per-op path tracing, dumps.

The paper's central claim is about *path distribution* — ABD reads/writes
(§10–§11) and All-aboard (§9) accelerate the common case while CP (§4–§8)
absorbs RMW conflicts.  This package makes that distribution a first-class
observable:

* :class:`~repro.obs.registry.MetricsRegistry` — one named surface for
  counters, gauges (pushed or lazily sampled) and histograms (backed by
  :class:`repro.serve.loadgen.sketch.QuantileSketch`);
* :class:`~repro.obs.trace.FlightRecorder` — a virtual-time span tracer
  with a bounded ring buffer: per-op lifecycle spans classified by the
  path the op actually took (``abd_read`` / ``abd_write`` /
  ``all_aboard_fast`` / ``cp_slow``), plus protocol events (retries,
  steals, helps, quorum-wait ticks, machine crashes);
* :class:`~repro.obs.clock.HostClock` — the recorder's wall-clock
  companion: nested host spans inside the served tick (total and self
  nanoseconds per span name), read through ``ClusterEngine.telemetry()``
  and kept out of dumps;
* :mod:`~repro.obs.dump` — deterministic JSONL and Chrome-trace/Perfetto
  exports of the ring, and :func:`~repro.obs.dump.flight_guard` which
  dumps automatically when a checker fails or a smoke script dies;
* :mod:`~repro.obs.report` — the summarizer behind
  ``scripts/trace_report.py`` (path mix, fast-path hit rate, per-path
  latency percentiles, top contended keys).

Zero-cost-by-default contract: a :class:`~repro.core.node.Machine` whose
``obs`` attribute is ``None`` (the default) pays nothing beyond an
``is not None`` branch per already-counted protocol event; path counters
are exact whenever a recorder is attached, while span *recording* into
the ring is governed by the recorder mode (``off`` / ``sampled`` /
``full``).  See ``docs/observability.md``.
"""

from .clock import HostClock
from .registry import MetricsRegistry
from .trace import PATHS, FlightRecorder, Span
from .dump import dump_all, dump_chrome_trace, dump_jsonl, flight_guard
from .report import load_records, summarize, render_summary

__all__ = [
    "HostClock", "MetricsRegistry", "FlightRecorder", "Span", "PATHS",
    "dump_all", "dump_chrome_trace", "dump_jsonl", "flight_guard",
    "load_records", "summarize", "render_summary",
]
