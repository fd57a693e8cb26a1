"""The plain reference: a sequential register per key, and the check.

The store promises linearizable reads, writes and fetch-and-adds, each
acknowledged once it is committed at a quorum of replicas.  The reference
is the sequential register those promises describe, written out plainly:
a key starts at value 0, a write sets it, an FAA returns the value before
it and adds its argument, a read returns it.  It imports nothing of the
program.

What the run hands it:

* every op the benchmark's client loop submitted: key, kind, argument, the
  cluster step before which it was submitted and the step in which its
  completion appeared (both counted by the client loop), and what the client
  got back: the value and the carstamp, the version the store gives every
  committed state (``(version, replica, log_no)``, ordered as a tuple);
* the register state of every replica after the drain, read back from the
  device: value and carstamp planes over every lane.

The carstamps are the store's claim of a linearization order.  The check
replays the reference register along that order and holds the run to it:

* ``value_mismatch``: a read or FAA whose returned value is not what the
  reference returns at its place in the order, or a read of a carstamp
  no update produced;
* ``order_violation``: an op that completed before another was submitted
  but sits after it in the order, or two updates claiming one carstamp;
* ``replica_mismatch``: a key on which fewer than a quorum of replicas
  hold the reference's final state, or a replica holds a state the
  reference never passed through (lanes never touched must hold the
  initial state);
* ``unfinished``: an op that neither completed nor was lost to a crash.

An op lost to a crash may or may not have taken effect.  Such "ghost"
ops are used only to explain values the reference cannot otherwise reach
on their key (an FAA ghost adds its argument, a ghost write installs its
value), and keys they touch are held only to agreement of a quorum on a
state at or after the last acknowledged one.

Every number compared must be 0: the comparison is exact.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

ZERO_CS = (0, -1, 0)            # carstamp of the initial value: (0, -1), 0
LIMITS = {"value_mismatch": 0, "order_violation": 0,
          "replica_mismatch": 0, "unfinished": 0}
UPDATES = ("write", "rmw")


def _ghost_path(got: int, cur: int, ghosts: dict, before: int,
                consume: bool) -> Optional[List[int]]:
    """Can lost ops submitted before step ``before`` turn ``cur`` into
    ``got``?  FAA ghosts add their argument; a ghost write installs its
    value, possibly followed by FAA ghosts.  Returns the values passed
    through (ending at ``got``), or None."""
    faa = [g for g in ghosts["rmw"] if g["submit_step"] < before]
    writes = [g for g in ghosts["write"] if g["submit_step"] < before]
    for base, w in [(cur, None)] + [(g["value"], g) for g in writes]:
        path, v, used = ([] if w is None else [base]), base, []
        for g in faa:
            if v >= got:
                break
            v += g["value"]
            path.append(v)
            used.append(g)
        if v == got and path:
            if consume:
                for g in used:
                    ghosts["rmw"].remove(g)
                if w is not None:
                    ghosts["write"].remove(w)
            return path
    return None


def _realtime_violations(ops: List[dict]) -> int:
    """Ops Y for which some X with ``X.complete_step <= Y.submit_step``
    (X completed before Y was submitted) sits later in the carstamp
    order: order key ``(cs, 0)`` for updates and ``(cs, 1)`` for reads,
    since a read of a state comes after the update that made it."""
    def order(o):
        return (o["cs"], 0 if o["kind"] in UPDATES else 1)
    by_done = sorted(ops, key=lambda o: o["complete_step"])
    done = [o["complete_step"] for o in by_done]
    prefix_max, best = [], None
    for o in by_done:
        k = order(o)
        best = k if best is None or k > best else best
        prefix_max.append(best)
    bad = 0
    for y in ops:
        n = bisect.bisect_right(done, y["submit_step"])
        if n and prefix_max[n - 1] > order(y):
            bad += 1
    return bad


def check_key(ops: List[dict], ghosts: dict) -> Tuple[Dict[str, int], dict]:
    """Check one key's completed ops; returns the counts and the
    reference's states ``{cs: value}`` with its final state."""
    counts = {"value_mismatch": 0, "order_violation": 0}
    updates = sorted((o for o in ops if o["kind"] in UPDATES),
                     key=lambda o: o["cs"])
    cur, states = 0, {ZERO_CS: 0}
    between: Dict[tuple, List[int]] = {}    # ghost states below a cs
    for u in updates:
        if u["cs"] in states:
            counts["order_violation"] += 1          # a carstamp taken twice
        if u["kind"] == "rmw":
            if u["got"] != cur:
                path = _ghost_path(u["got"], cur, ghosts, u["complete_step"],
                                   consume=True)
                if path is None:
                    counts["value_mismatch"] += 1
                else:
                    between[u["cs"]] = path
            cur = u["got"] + u["value"]
        else:
            cur = u["value"]
        states[u["cs"]] = cur
    cs_sorted = sorted(states)
    for r in (o for o in ops if o["kind"] == "read"):
        want = states.get(r["cs"])
        if want is None:
            # a state only a lost op can have made: one the reference
            # passed through below the next known carstamp, or one the
            # lost ops left can make from the last state below it
            i = bisect.bisect_left(cs_sorted, r["cs"])
            nxt = cs_sorted[i] if i < len(cs_sorted) else None
            if r["got"] not in between.get(nxt, ()) and _ghost_path(
                    r["got"], states[cs_sorted[i - 1]], ghosts,
                    r["complete_step"], consume=False) is None:
                counts["value_mismatch"] += 1
        elif r["got"] != want:
            counts["value_mismatch"] += 1
    counts["order_violation"] += _realtime_violations(ops)
    final_cs = cs_sorted[-1] if updates else ZERO_CS
    return counts, {"states": states, "final": (final_cs, cur)}


def check(ops: List[dict], planes: Dict[str, np.ndarray],
          quorum: int) -> Dict[str, int]:
    """Hold a run to the reference.

    ``ops``: the client loop's op records (``key``, ``kind``, ``value``,
    ``submit_step``, and once done ``complete_step``, ``got``, ``cs``;
    ``lost`` when a crash killed it).  ``planes``: ``value``,
    ``base_v``, ``base_m`` and ``val_log``, each (replicas, lanes), read
    from the device after the drain."""
    out = dict.fromkeys(LIMITS, 0)
    per_key: Dict[int, List[dict]] = defaultdict(list)
    ghosts: Dict[int, dict] = defaultdict(lambda: {"rmw": [], "write": []})
    for o in ops:
        if o.get("complete_step") is not None:
            per_key[o["key"]].append(o)
        elif o.get("lost"):
            if o["kind"] in UPDATES:
                ghosts[o["key"]][o["kind"]].append(o)
        else:
            out["unfinished"] += 1
    value = planes["value"]
    cs = np.stack([planes["base_v"], planes["base_m"], planes["val_log"]],
                  axis=-1)
    touched = np.zeros(value.shape[1], bool)
    for key in set(per_key) | set(ghosts):
        touched[key] = True
        counts, ref = check_key(per_key.get(key, []), ghosts[key])
        for k, v in counts.items():
            out[k] += v
        held = [(tuple(int(x) for x in cs[m, key]), int(value[m, key]))
                for m in range(value.shape[0])]
        final_cs, final_val = ref["final"]
        if ghosts[key]["rmw"] or ghosts[key]["write"]:
            # unexplained ghosts may have landed after the last ack
            ok = any(c >= final_cs and held.count((c, v)) >= quorum
                     for c, v in held)
        else:
            ok = (held.count((final_cs, final_val)) >= quorum
                  and all(ref["states"].get(c) == v for c, v in held))
        out["replica_mismatch"] += not ok
    init = ((value == 0) & (cs == np.array(ZERO_CS)).all(-1)).all(0)
    out["replica_mismatch"] += int((~init & ~touched).sum())
    return out


def failures(numbers: Dict[str, int]) -> Optional[str]:
    """None when every number is within its limit."""
    bad = [f"{k}={numbers[k]} (limit {lim})" for k, lim in LIMITS.items()
           if numbers[k] > lim]
    return "; ".join(bad) if bad else None
