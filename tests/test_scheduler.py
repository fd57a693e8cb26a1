"""Ingest-scheduler invariants (repro.serve.paxos.scheduler).

Deterministic unit tests for the engine contract (conflict-free batches,
per-key FIFO, the registry rule, batch-size targets, strict-order
equivalence with the replay bucketer), plus hypothesis property tests for
the fairness claims: under adversarial key skew no admitted item starves
(every emitted batch contains the globally oldest pending item), batches
never contain a key conflict, and batch-size targets are respected.
"""

import pytest

from repro.core.types import Msg, MsgKind, RmwId, TS
from repro.serve.paxos.scheduler import IngestScheduler, bucket_conflict_free


def msg(kind, key, cnt=0, gsess=-1, seq=0):
    return Msg(kind, src=0, key=key, ts=TS(3, 0),
               rmw_id=RmwId(cnt, gsess), lid=seq)


def propose(key, cnt=1, gsess=0):
    return msg(MsgKind.PROPOSE, key, cnt, gsess)


def commit(key, cnt=1, gsess=0):
    return msg(MsgKind.COMMIT, key, cnt, gsess)


# ---------------------------------------------------------------------------
# deterministic contract tests
# ---------------------------------------------------------------------------

def test_strict_drain_matches_bucket_conflict_free():
    trace = [propose(0), propose(1), propose(0), commit(2, cnt=3, gsess=1),
             propose(3, cnt=2, gsess=1), propose(3, cnt=9, gsess=1),
             commit(0), propose(1, cnt=1, gsess=0)]
    sched = IngestScheduler(strict_order=True)
    for m in trace:
        sched.offer(m)
    assert list(sched.drain()) == bucket_conflict_free(trace)


def test_registry_rule_splits_batch():
    # a commit registering (3, gsess 1) must not share a batch with a later
    # PROPOSE reading rmw-id (2, gsess 1): registered-ness would be stale
    trace = [commit(0, cnt=3, gsess=1), propose(1, cnt=2, gsess=1)]
    batches = bucket_conflict_free(trace)
    assert [len(b) for b in batches] == [1, 1]
    # a higher counter is not registered by it -> same batch is fine
    trace2 = [commit(0, cnt=3, gsess=1), propose(1, cnt=4, gsess=1)]
    assert len(bucket_conflict_free(trace2)) == 1


def test_batch_target_caps_emission():
    sched = IngestScheduler(batch_target=3, strict_order=True)
    for key in range(10):
        sched.offer(propose(key))
    sizes = [len(b) for b in sched.drain()]
    assert sizes == [3, 3, 3, 1]


def test_aging_mode_lets_cold_keys_overtake():
    # strict mode stalls behind the hot key; aging mode packs cold keys
    # into the same batches
    trace = [propose(0), propose(0), propose(0), propose(1), propose(2)]
    strict = IngestScheduler(strict_order=True)
    aging = IngestScheduler(strict_order=False)
    for m in trace:
        strict.offer(m)
        aging.offer(m)
    assert [len(b) for b in strict.drain()] == [1, 1, 3]
    assert [len(b) for b in aging.drain()] == [3, 1, 1]


def test_key_of_for_generic_items():
    sched = IngestScheduler(key_of=lambda item: item[0])
    sched.offer(("sess0", "a"))
    sched.offer(("sess1", "b"))
    sched.offer(("sess0", "c"))
    batches = list(sched.drain())
    assert batches == [[("sess0", "a"), ("sess1", "b")], [("sess0", "c")]]


def test_non_msg_without_key_of_raises():
    with pytest.raises(TypeError):
        IngestScheduler().offer(("no", "lane"))


# ---------------------------------------------------------------------------
# hypothesis properties (the deterministic tests above run without it —
# the guarded-import pattern keeps this module partially collectable)
# ---------------------------------------------------------------------------

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:                                  # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS,
    reason="scheduler property tests need hypothesis (pip install -r "
           "requirements-dev.txt)")

if HAVE_HYPOTHESIS:
    KINDS = [MsgKind.PROPOSE, MsgKind.ACCEPT, MsgKind.COMMIT,
             MsgKind.READ_COMMIT, MsgKind.WRITE, MsgKind.READ_QUERY]

    # adversarial key skew: key 0 is drawn an order of magnitude more often
    skewed_key = st.one_of(st.just(0), st.just(0), st.just(0),
                           st.integers(min_value=0, max_value=7))
    msgs = st.lists(
        st.builds(lambda kind, key, cnt, gsess: msg(kind, key, cnt, gsess),
                  st.sampled_from(KINDS), skewed_key,
                  st.integers(min_value=1, max_value=3),
                  st.integers(min_value=-1, max_value=3)),
        max_size=120)
    targets = st.one_of(st.none(), st.integers(min_value=1, max_value=6))
    modes = st.booleans()


def _reg_would_see_stale(batch):
    """True if any PROPOSE/ACCEPT shares a batch with an *earlier* commit
    that registered its rmw-id (the in-batch visibility hazard)."""
    reg = {}
    for m in batch:
        if (m.kind in (MsgKind.PROPOSE, MsgKind.ACCEPT)
                and m.rmw_id.gsess >= 0
                and reg.get(m.rmw_id.gsess, -1) >= m.rmw_id.counter):
            return True
        if (m.kind in (MsgKind.COMMIT, MsgKind.READ_COMMIT)
                and m.rmw_id.gsess >= 0):
            reg[m.rmw_id.gsess] = max(reg.get(m.rmw_id.gsess, -1),
                                      m.rmw_id.counter)
    return False


if HAVE_HYPOTHESIS:
    @needs_hypothesis
    @settings(max_examples=120, deadline=None)
    @given(trace=msgs, target=targets, strict=modes)
    def test_batches_conflict_free_and_fifo(trace, target, strict):
        sched = IngestScheduler(batch_target=target, strict_order=strict)
        for m in trace:
            sched.offer(m)
        emitted = []
        per_key_in = {}
        for i, m in enumerate(trace):
            per_key_in.setdefault(m.key, []).append(i)
        order = {id(m): i for i, m in enumerate(trace)}
        per_key_out = {}
        for batch in sched.drain():
            assert batch, "drain must never emit an empty batch"
            if target is not None:
                assert len(batch) <= target, "batch-size target violated"
            keys = [m.key for m in batch]
            assert len(keys) == len(set(keys)), "key conflict inside a batch"
            assert not _reg_would_see_stale(batch), "registry rule violated"
            for m in batch:
                per_key_out.setdefault(m.key, []).append(order[id(m)])
            emitted.extend(batch)
        assert len(emitted) == len(trace), "scheduler lost/duplicated items"
        for key, seq in per_key_out.items():
            assert seq == per_key_in[key], f"per-key FIFO broken ({key})"

    @needs_hypothesis
    @settings(max_examples=120, deadline=None)
    @given(trace=msgs, target=targets)
    def test_no_starvation_under_key_skew(trace, target):
        """Aging fairness: every emitted batch contains the globally oldest
        pending item — a hot key can never starve a cold key's request."""
        sched = IngestScheduler(batch_target=target, strict_order=False)
        for m in trace:
            sched.offer(m)
        pending = list(trace)
        for batch in sched.drain():
            assert pending[0] in batch, "oldest pending item starved"
            for m in batch:
                pending.remove(m)
        assert not pending

    @needs_hypothesis
    @settings(max_examples=60, deadline=None)
    @given(trace=msgs)
    def test_strict_mode_is_the_replay_bucketer(trace):
        sched = IngestScheduler(strict_order=True)
        for m in trace:
            sched.offer(m)
        assert list(sched.drain()) == bucket_conflict_free(trace)


# ---------------------------------------------------------------------------
# observability: gauges, bind_metrics, reset (crash-stop counter hygiene)
# ---------------------------------------------------------------------------

def test_gauges_track_queue_state():
    sched = IngestScheduler(strict_order=True)
    assert sched.gauges() == {"queue_depth": 0, "keys_backlogged": 0,
                              "oldest_age": 0}
    sched.offer(propose(0))
    sched.offer(propose(1))
    sched.offer(propose(0))
    g = sched.gauges()
    assert g["queue_depth"] == 3
    assert g["keys_backlogged"] == 2
    # the oldest pending item was admitted 3 admissions ago
    assert g["oldest_age"] == 3
    for _ in sched.drain():
        pass
    assert sched.gauges() == {"queue_depth": 0, "keys_backlogged": 0,
                              "oldest_age": 0}


def test_gauges_after_partial_emission():
    # conflicting items on one key: strict mode emits one per batch
    sched = IngestScheduler(strict_order=True)
    for _ in range(4):
        sched.offer(propose(0))
    sched.emit()
    g = sched.gauges()
    assert g["queue_depth"] == 3
    assert g["keys_backlogged"] == 1
    assert g["oldest_age"] == 3          # head arrived 3 admissions back


def test_bound_gauges_publish_once_per_emitted_batch():
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    sched = IngestScheduler(strict_order=True)
    sched.bind_metrics(reg, "ingest.m0")
    for _ in range(3):
        sched.offer(propose(0))          # conflicts: three batches
    sched.offer(propose(1))
    depths = []
    for _ in sched.drain():
        # live readings, published as each batch was emitted
        depths.append(reg.gauge("ingest.m0.queue_depth"))
        assert depths[-1] == sched.pending()
    assert depths == [3, 2, 0]
    hist = reg.snapshot()["histograms"]["ingest.m0.batch_lanes"]
    assert hist["count"] == sched.stats["batches"] == 3


def test_reset_clears_state_keeps_stats():
    """Counter-hygiene regression: an abandoned drain_sharded generator
    (machine crashed mid-wave) must not leave stale backlog behind."""
    from repro.core.lanes import ShardMap

    sched = IngestScheduler(strict_order=True)
    for _ in range(3):
        sched.offer(propose(0))          # same key: one item per batch
    sched.offer(propose(1))
    gen = sched.drain_sharded(ShardMap(n_shards=2, n_lanes=8))
    batch, shards = next(gen)            # consume one batch, then abandon
    assert batch
    gen.close()
    stale = sched.gauges()
    assert stale["queue_depth"] > 0      # the stale state the bug leaked
    stats_before = dict(sched.stats)
    sched.reset()
    assert sched.gauges() == {"queue_depth": 0, "keys_backlogged": 0,
                              "oldest_age": 0}
    assert sched.pending() == 0
    # cumulative stats describe history and survive the reset
    assert sched.stats == stats_before
    # the scheduler stays usable: fresh offers drain normally
    sched.offer(propose(5))
    assert [m.key for b in sched.drain() for m in b] == [5]


def test_offer_many_partial_failure():
    """Regression: ``offer_many`` dying mid-iteration must commit the
    admitted prefix.  The old code left ``_seq`` (and the pending /
    backlog counters) unbumped on the error path, so the *next*
    admissions reused sequence numbers — and a stale heap entry for a
    long-dead key could alias a live head's seq, making :meth:`gauges`
    report the dead key's ``oldest_age`` and drift ``queue_depth``
    negative under key churn."""
    def boom_key(item):
        if item == "boom":
            raise RuntimeError("boom")
        return item[0]

    sched = IngestScheduler(key_of=boom_key)
    sched.offer(("a", "x"))                       # seq 0
    with pytest.raises(RuntimeError):
        sched.offer_many([("b", "y"), ("a", "z"), "boom", ("c", "!")])
    # the two items admitted before the failure are committed
    assert sched.gauges() == {"queue_depth": 3, "keys_backlogged": 2,
                              "oldest_age": 3}
    # their sequence numbers are burned: no later admission can alias them
    assert sched._seq == 3
    sched.offer(("c", "w"))                       # fresh seq 3, not a reuse
    assert sched.gauges()["queue_depth"] == 4
    drained = [it for b in sched.drain() for it in b]
    assert sorted(drained) == [("a", "x"), ("a", "z"), ("b", "y"),
                               ("c", "w")]
    assert sched.gauges() == {"queue_depth": 0, "keys_backlogged": 0,
                              "oldest_age": 0}


def test_dead_keys_do_not_leak_queues():
    """Regression: an emptied per-key deque is deleted, not kept — under
    key churn the old behavior leaked one empty deque per key ever seen
    (and those corpses were what stale heap entries resolved against)."""
    sched = IngestScheduler(key_of=lambda item: item)
    for key in range(1000):
        sched.offer(key)
        assert [b for b in sched.drain()] == [[key]]
    assert len(sched._queues) == 0
    assert sched._heads == []
    assert sched.gauges() == {"queue_depth": 0, "keys_backlogged": 0,
                              "oldest_age": 0}


def test_emit_sharded_bad_key_keeps_deferred_heads():
    """Regression: a key outside the sharded lane axis raises, but the
    heads already deferred by the conflict scan this pass must survive —
    dropping them stranded their queues forever."""
    from repro.core.lanes import ShardMap

    sched = IngestScheduler()                     # aging mode: defers
    sched.offer(propose(1))
    sched.offer(propose(1))                       # conflicts -> deferred
    sched.offer(propose(200))                     # outside the lane axis
    with pytest.raises(ValueError):
        sched.emit_sharded(ShardMap(n_shards=2, n_lanes=8))
    # one item (key 1 head) was admitted before the raise; everything
    # else — the deferred second key-1 item and the bad-key item — must
    # still drain
    remaining = [m.key for b in sched.drain() for m in b]
    assert sorted(remaining) == [1, 200]
    assert sched.gauges() == {"queue_depth": 0, "keys_backlogged": 0,
                              "oldest_age": 0}


def test_gauges_match_oracle_under_key_churn():
    """Deterministic churn fuzz: a sliding key window (constant key
    birth/death), mid-iteration offer_many failures and interleaved
    emission, checked against a straight-line oracle after every step.
    This is the workload that exposed the stale-heap aliasing."""
    import random

    rng = random.Random(0xA5)
    sched = IngestScheduler(key_of=lambda item: item[0])
    model = {}                   # key -> seqs, mirroring the live queues
    seq = 0
    base = 0

    def admit(key):
        nonlocal seq
        item = (key, seq)
        model.setdefault(key, []).append(seq)
        seq += 1
        return item

    def retire(item):
        key, s = item
        model[key].remove(s)
        if not model[key]:
            del model[key]

    for _step in range(1500):
        r = rng.random()
        if r < 0.45:
            if rng.random() < 0.3:
                base += 1                        # slide the key window
            sched.offer(admit(base + rng.randrange(6)))
        elif r < 0.60:
            def gen(n_ok):
                for _ in range(n_ok):
                    yield admit(base + rng.randrange(6))
                raise RuntimeError("mid-iteration failure")
            with pytest.raises(RuntimeError):
                sched.offer_many(gen(rng.randrange(4)))
        elif r < 0.90:
            for item in sched.emit():
                retire(item)
        else:
            for batch in sched.drain():
                for item in batch:
                    retire(item)
        depth = sum(len(v) for v in model.values())
        oldest = ((seq - min(s for v in model.values() for s in v))
                  if model else 0)
        assert sched.gauges() == {"queue_depth": depth,
                                  "keys_backlogged": len(model),
                                  "oldest_age": oldest}
    assert len(sched._queues) == len(model)


def test_bind_metrics_one_gauge_surface():
    """bind_metrics re-homes the gauge surface onto a MetricsRegistry:
    after each emitted batch the registry holds what gauges() reads."""
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    sched = IngestScheduler(strict_order=True)
    sched.bind_metrics(reg, "ingest.m7")
    for _ in range(3):
        sched.offer(propose(0))                   # conflicts: three batches
    sched.offer(propose(1))
    for _ in sched.drain():
        g = sched.gauges()
        for name in ("queue_depth", "keys_backlogged", "oldest_age"):
            assert reg.gauge("ingest.m7." + name) == g[name]
    assert reg.gauge("ingest.m7.queue_depth") == 0
    hist = reg.snapshot()["histograms"]["ingest.m7.batch_lanes"]
    assert hist["count"] == sched.stats["batches"]


def test_batched_machine_crash_resets_ingest():
    """Mid-batch crash: staged ingest dies with the inbox, and the dead
    machine's scheduler reports empty gauges to observers."""
    from repro.core.node import ProtocolConfig
    from repro.core.sim import Cluster, NetConfig
    from repro.serve.paxos import BatchedMachine

    cl = Cluster(ProtocolConfig(n_machines=3, sessions_per_machine=2),
                 NetConfig(seed=3), machine_cls=BatchedMachine)
    for s in range(2):
        cl.rmw(0, s, key=s)
    cl.step(2)                           # traffic in flight
    m = cl.machines[1]
    # stage items as a mid-wave abort would leave them: offered but not
    # drained when the tick dies
    m.ingest.offer(propose(0))
    m.ingest.offer(propose(1, cnt=2))
    assert m.ingest.gauges()["queue_depth"] == 2
    cl.crash(1)
    assert cl.machines[1].ingest.gauges() == {
        "queue_depth": 0, "keys_backlogged": 0, "oldest_age": 0}
    assert cl.machines[1].ingest.pending() == 0
