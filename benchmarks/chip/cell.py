"""One run of one cell: build the served cluster, warm it, measure, check.

The system under test is the served register store as users get it:
``Cluster(ProtocolConfig, NetConfig, machine_cls=BatchedMachine)`` with
the served path's defaults, its fused ``ClusterEngine`` holding every
replica's planes on the device, and the KV plane sized to the whole key
universe before the first op.  One cluster step is one tick of the
simulated network, whose messages take ``net_delay_ticks`` ticks per hop,
so a latency here is a count of steps times the wall time of each.

A run goes through these phases, in order:

1. set-up: build the cluster, size the plane, turn the compile cache on,
   then warm up in the closed loop until the cluster has completed as
   many ops as there are client sessions (this compiles every fused step);
2. the window: ``seconds`` on the host clock, driven by the mix's loop
   and fault schedule; with ``trace`` a short profiled slice sits inside
   it and host-clock readings leave that slice out;
3. the drain: no new ops; step until every live session is idle and the
   network is empty;
4. the check (``reference.py``) on what clients got back and on the
   replicas' register planes read back from the device.
"""

from __future__ import annotations

import dataclasses
import random
import statistics
import time
from typing import Dict, List, Optional

import numpy as np

import reference
import roofline
import traffic as traffic_mod

# the profiled slice of a traced run: where it starts in the window and
# how long it lasts, as shares of the window, and its longest length
SLICE_AT, SLICE_SHARE, SLICE_MAX_S = 0.4, 0.2, 3.0
DRAIN_MAX_STEPS, DRAIN_MAX_S = 20_000, 150.0
SPAN = "bench."                     # prefix of every host span placed here


class CompileClock:
    """Backend compiles while installed, from ``jax.monitoring``.  JAX
    reports a program loaded from the persistent cache as a backend
    compile too, so those are counted apart (``cache_hits``) and left out
    of ``compiles``."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.loads = 0
        self.cache_hits = 0

    @property
    def compiles(self) -> int:
        return self.loads - self.cache_hits

    def _duration(self, event, duration, **_):
        self.loads += event == self._COMPILE

    def _event(self, event, **_):
        self.cache_hits += event == self._HIT

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


@dataclasses.dataclass
class Window:
    """What the window measured, for the result line and the readers of
    per-layer metrics (``metrics/<name>.py``)."""

    seconds: float = 0.0                 # host-clock length of the window
    ops: int = 0                         # completions inside it
    attempted: int = 0                   # ops submitted inside it
    failed: int = 0                      # of those, lost or never done
    latencies_ms: List[float] = dataclasses.field(default_factory=list)
    ticks: int = 0
    # host clock outside the profiled slice (all of it untraced)
    host_s: float = 0.0
    host_ticks: int = 0
    compiles: int = 0
    lateness_ms: float = 0.0             # open loop: worst submit delay
    telemetry: Dict[str, int] = dataclasses.field(default_factory=dict)
    paths: Dict[str, int] = dataclasses.field(default_factory=dict)
    call_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Optional[object] = None       # tracefile.Reduction
    peaks: Dict[str, float] = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0


def _quantile(xs: List[float], q: float) -> float:
    """The q-quantile of all samples (linear between order statistics)."""
    s = sorted(xs)
    if len(s) == 1:
        return s[0]
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def end_to_end(w: Window) -> Dict[str, float]:
    return {"ops_per_s": w.ops / w.seconds,
            "commit_p50_ms": statistics.median(w.latencies_ms),
            "commit_p95_ms": _quantile(w.latencies_ms, 0.95)}


class Run:
    """The client loop: a client population over one served cluster."""

    def __init__(self, config: dict, traffic: dict, seed: int, *,
                 trace: bool = False, trace_dir: Optional[str] = None):
        from repro.core.node import ProtocolConfig
        from repro.core.sim import Cluster, NetConfig
        from repro.serve.paxos import BatchedMachine

        self.config, self.traffic, self.seed = config, traffic, seed
        self.trace, self.trace_dir = trace, trace_dir
        lo, hi = config["net_delay_ticks"]
        pcfg = ProtocolConfig(n_machines=config["replicas"],
                              sessions_per_machine=config[
                                  "sessions_per_replica"],
                              all_aboard=config["all_aboard"])
        self.quorum = config["replicas"] // 2 + 1
        self.cluster = Cluster(pcfg, NetConfig(seed=seed, min_delay=lo,
                                               max_delay=hi),
                               machine_cls=BatchedMachine)
        self.engine = self.cluster.engine
        self.cluster.machines[0].kvs.ensure(config["recordcount"] - 1)
        self.recorder = None
        if trace:
            from repro.obs import FlightRecorder
            self.recorder = FlightRecorder(mode="off")
            self.cluster.attach_obs(self.recorder)
        self.stream = traffic_mod.OpStream(traffic, config, seed)
        self.route = random.Random(f"route:{seed}")
        # terminal t runs at replica t % replicas (traffic.py)
        reps = config["replicas"]
        self.sessions = [(t % reps, t // reps) for t in
                         range(reps * config["sessions_per_replica"])]
        self.outstanding = {ms: 0 for ms in self.sessions}
        self.done_per_session = {ms: 0 for ms in self.sessions}
        self.ops: Dict[int, dict] = {}
        self._cursor = 0
        self._down = set()                   # crashed replicas
        self._calls = {"_fused_receiver_step": [0, 0],
                       "_fused_issuer_step": [0, 0]}

    # -- client side ---------------------------------------------------------

    def _submit(self, mid: int, sess: int, t: float,
                due: Optional[float] = None) -> None:
        from repro.core.node import ReqKind, Request
        from repro.core.types import RmwOp
        op = self.stream.next(sess * self.config["replicas"] + mid)
        kind, key, value = op["kind"], op["key"], op["value"]
        if kind == "rmw":
            req = Request(ReqKind.RMW, key, op=RmwOp.FAA, arg1=value)
        elif kind == "write":
            req = Request(ReqKind.WRITE, key, value=value)
        else:
            req = Request(ReqKind.READ, key)
        tag = self.cluster.submit(mid, sess, req)
        self.ops[tag] = {"key": key, "kind": kind, "value": value,
                         "mid": mid, "sess": sess,
                         "submit_step": self.cluster.rounds,
                         "t_submit": t if due is None else due,
                         "t_sent": t, "complete_step": None}
        self.outstanding[(mid, sess)] += 1

    def _refill(self, t: float) -> None:
        n = int(self.traffic.get("outstanding", 1))
        for ms in self.sessions:
            while self.outstanding[ms] < n and ms[0] not in self._down:
                self._submit(ms[0], ms[1], t)

    def _step(self) -> float:
        self.cluster.step()
        t = time.perf_counter()
        hist = self.cluster.history
        while self._cursor < len(hist):
            h = hist[self._cursor]
            self._cursor += 1
            rec = self.ops[h["tag"]]
            cs = h["carstamp"]
            rec.update(complete_step=self.cluster.rounds, t_done=t,
                       got=int(h["value"]),
                       cs=(int(cs.base.version), int(cs.base.mid),
                           int(cs.log_no)))
            ms = (rec["mid"], rec["sess"])
            self.outstanding[ms] -= 1
            self.done_per_session[ms] += 1
        return t

    def _fault(self, ev: dict) -> None:
        action = ev["action"]
        if action == "crash":
            mid = int(ev["replica"])
            self.cluster.crash(mid)
            self._down.add(mid)
            for rec in self.ops.values():
                if rec["mid"] == mid and rec["complete_step"] is None:
                    rec["lost"] = True
            for ms in self.sessions:
                if ms[0] == mid:
                    self.outstanding[ms] = 0
        elif action == "restart":
            mid = int(ev["replica"])
            self.cluster.restart(mid)
            self._down.discard(mid)
        elif action == "partition":
            a, b = ev["groups"]
            self.cluster.network.partition(a, b)
        else:
            self.cluster.network.heal()

    # -- instrumentation of a traced run (spans placed from outside) ---------

    def _instrument(self):
        """Wrap the fused steps and the engine tick in host spans, and
        record the bytes of every fused call's operands and results."""
        import jax
        from repro.serve.paxos import cluster_engine as ce
        saved = {n: getattr(ce, n) for n in self._calls}

        def wrap(name, fn):
            def call(*args, **kw):
                with jax.profiler.TraceAnnotation(SPAN + name.strip("_")):
                    out = fn(*args, **kw)
                c = self._calls[name]
                c[0] += 1
                c[1] += roofline.call_bytes(args, out)
                return out
            return call

        for n, fn in saved.items():
            setattr(ce, n, wrap(n, fn))
        step_all = self.engine.step_all

        def traced_step_all(*a, **kw):
            with jax.profiler.TraceAnnotation(SPAN + "step_all"):
                return step_all(*a, **kw)
        self.engine.step_all = traced_step_all

        def restore():
            for n, fn in saved.items():
                setattr(ce, n, fn)
            del self.engine.step_all
        return restore

    # -- the run ---------------------------------------------------------------

    def warm_up(self) -> None:
        """Closed loop, one op per session, until the cluster has completed
        as many ops as there are sessions: every fused step is compiled by
        then, and the work done is the same amount from every seed (the
        slowest session's first op would make set-up swing with it)."""
        t = time.perf_counter()
        for mid, sess in self.sessions:
            self._submit(mid, sess, t)
        while sum(self.done_per_session.values()) < len(self.sessions):
            self._step()
            for ms in self.sessions:
                if self.outstanding[ms] == 0:
                    self._submit(ms[0], ms[1], time.perf_counter())

    def window(self, seconds: float) -> Window:
        import jax
        w = Window()
        restore = self._instrument() if self.trace else None
        faults = sorted(self.traffic.get("faults", []),
                        key=lambda e: float(e["at_s"]))
        closed = self.traffic["loop"] == "closed"
        due = ([] if closed else
               traffic_mod.arrival_offsets(self.traffic, self.seed, seconds))
        tel0 = dict(self.engine.telemetry())
        paths0 = self.recorder.path_counts() if self.recorder else {}
        slice_at = SLICE_AT * seconds
        slice_len = min(SLICE_MAX_S, SLICE_SHARE * seconds)
        sl = {"state": 0}
        fi = ai = 0
        t0 = time.perf_counter()
        tick_t = t0
        with CompileClock() as clock:
            while True:
                now = time.perf_counter()
                el = now - t0
                if el >= seconds:
                    break
                if self.trace and sl["state"] == 0 and el >= slice_at:
                    sl.update(state=1, t0=now, ticks=self.cluster.rounds)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0   # host spans, no calls
                    jax.profiler.start_trace(self.trace_dir,
                                             profiler_options=opts)
                    sl["ann"] = jax.profiler.TraceAnnotation(SPAN + "slice")
                    sl["ann"].__enter__()
                elif sl["state"] == 1 and el >= slice_at + slice_len:
                    sl["ann"].__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    sl.update(state=2, t1=time.perf_counter(),
                              ticks=self.cluster.rounds - sl["ticks"])
                while fi < len(faults) and float(faults[fi]["at_s"]) <= el:
                    self._fault(faults[fi])
                    fi += 1
                if closed:
                    self._refill(now)
                else:
                    live = [ms for ms in self.sessions
                            if ms[0] not in self._down]
                    while ai < len(due) and due[ai] <= el and live:
                        mid, sess = live[self.route.randrange(len(live))]
                        self._submit(mid, sess, now, due=t0 + due[ai])
                        w.lateness_ms = max(w.lateness_ms,
                                            (el - due[ai]) * 1e3)
                        ai += 1
                if self.trace and sl["state"] == 1:
                    with jax.profiler.TraceAnnotation(SPAN + "cluster_step"):
                        tick_t = self._step()
                else:
                    tick_t = self._step()
                w.ticks += 1
        t_end = tick_t
        if sl["state"] == 1:                 # the window ended inside it
            sl["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            sl.update(state=2, t1=time.perf_counter(),
                      ticks=self.cluster.rounds - sl["ticks"])
        w.seconds = t_end - t0
        w.compiles = clock.compiles
        traced_s = sl["t1"] - sl["t0"] if sl["state"] == 2 else 0.0
        w.host_s = w.seconds - traced_s
        w.host_ticks = w.ticks - (sl["ticks"] if sl["state"] == 2 else 0)
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        w.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
        tel1 = self.engine.telemetry()
        w.telemetry = {k: tel1[k] - tel0.get(k, 0) for k, v in tel1.items()
                       if isinstance(v, int)}
        if self.recorder is not None:
            p1 = self.recorder.path_counts()
            w.paths = {k: p1[k] - paths0.get(k, 0) for k in p1}
        for rec in self.ops.values():
            if t0 <= rec["t_sent"] < t_end:
                w.attempted += 1
            done = rec.get("t_done")
            if done is not None and t0 < done <= t_end:
                w.ops += 1
                w.latencies_ms.append((done - rec["t_submit"]) * 1e3)
        if restore is not None:
            restore()
        w.call_bytes = {n: c[1] / c[0] for n, c in self._calls.items()
                        if c[0]}
        self._window = (t0, t_end)
        return w

    def drain(self) -> None:
        t = time.perf_counter()
        for _ in range(DRAIN_MAX_STEPS):
            if self._quiet() or time.perf_counter() - t > DRAIN_MAX_S:
                return
            self._step()

    def _quiet(self) -> bool:
        if self.cluster.network.pending():
            return False
        for m in self.cluster.machines:
            if not m.alive or m.retired:
                continue
            if m.inbox or any(m.fifos):
                return False
            if not all(m.session_idle(s) for s in range(len(m.fifos))):
                return False
        return True

    def device_planes(self) -> Dict[str, np.ndarray]:
        """The replicas' register planes, read back from the device."""
        kv = self.engine.kv
        if kv.host_dirty or kv.dev is None:
            kv.push()                  # host writes since the last step
        dev = np.asarray(kv.dev)
        return {f: dev[kv.fields.index(f)]
                for f in ("value", "base_v", "base_m", "val_log")}

    def check(self) -> Dict[str, int]:
        planes = self.device_planes()
        ops = list(self.ops.values())
        self.cluster = self.engine = None    # free the program's state
        return reference.check(ops, planes, self.quorum)

    def failed_in_window(self) -> int:
        t0, t_end = self._window
        return sum(1 for r in self.ops.values()
                   if t0 <= r["t_sent"] < t_end
                   and r["complete_step"] is None)
