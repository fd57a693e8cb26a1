"""Traffic mixes: one JSON file per mix under ``traffic/``, one generator.

A mix file holds parameters only; every cell's traffic is made here from
them and from ``--seed``.  Keys:

* ``loop``: ``"closed"`` or ``"open"``.
  - closed: every client session keeps ``outstanding`` ops submitted
    (YCSB's client threads run with one and no think time).
  - open: ops arrive at ``rate_ops_per_s`` on the host clock, with
    Poisson (``"arrivals": "poisson"``) gaps, each to a random live
    replica and session; latency counts from the scheduled arrival.
* ``mix``: op-class shares ``read``, ``write`` and ``rmw`` (summing to 1).
  Writes are ABD writes; an RMW is ``rmw_op`` with ``rmw_arg``
  (``"FAA"``, 1: fetch-and-add +1).
* ``keys``: how each op class picks its key (``{"rmw": "home", ...}``;
  a class not named draws ``"zipf"``):
  - ``zipf``: Zipfian over the configuration's ``recordcount`` keys with
    its ``zipf_s``, by a copy of the program's inverse-CDF sampler with a
    seeded affine rank->key scatter (``serve/loadgen/zipf.py``), so a mix
    never depends on the program's own generator;
  - ``uniform``: uniform over the ``recordcount`` keys;
  - ``home``: uniform over the terminal's home group, the configuration's
    ``home_keys`` consecutive keys shared by ``terminals_per_home``
    consecutive terminals (TPC-C: a warehouse's 10 districts and its 10
    terminals);
  - ``own``: the terminal's own key in its home group.
* ``faults``: a list of ``{"at_s", "action", ...}`` events at offsets
  into the measured window: ``crash``/``restart`` with ``replica``,
  ``partition`` with ``groups`` (two lists of replicas), ``heal``.

A terminal is one client session: terminal ``t`` runs its ops at replica
``t % replicas`` as that replica's session ``t // replicas``.
"""

from __future__ import annotations

import json
import math
import pathlib
import random
from typing import List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
KINDS = ("read", "write", "rmw")
ACTIONS = ("crash", "restart", "partition", "heal")
KEY_RULES = ("zipf", "uniform", "home", "own")


def load(name: str, root: pathlib.Path = HERE) -> dict:
    """The mix named ``name`` from ``traffic/<name>.json``, validated."""
    return validate(json.loads((root / "traffic" / f"{name}.json")
                               .read_text()))


def validate(t: dict) -> dict:
    if t.get("loop") not in ("closed", "open"):
        raise ValueError(f"traffic loop must be closed or open: {t}")
    mix = t.get("mix", {})
    if set(mix) - set(KINDS) or not math.isclose(sum(mix.values()), 1.0):
        raise ValueError(f"traffic mix needs read/write/rmw shares "
                         f"summing to 1: {mix}")
    keys = t.get("keys", {})
    if set(keys) - set(KINDS) or set(keys.values()) - set(KEY_RULES):
        raise ValueError(f"traffic keys map op classes to {KEY_RULES}: "
                         f"{keys}")
    if t.get("rmw_op", "FAA") != "FAA":
        raise ValueError("only FAA RMWs are generated")
    if t["loop"] == "closed" and int(t.get("outstanding", 1)) < 1:
        raise ValueError("a closed loop needs outstanding >= 1")
    if t["loop"] == "open" and not float(t.get("rate_ops_per_s", 0)) > 0:
        raise ValueError("an open loop needs rate_ops_per_s > 0")
    for ev in t.get("faults", []):
        if ev.get("action") not in ACTIONS or float(ev.get("at_s", -1)) < 0:
            raise ValueError(f"bad fault event {ev}")
    return t


class ZipfKeys:
    """Zipf(s) over ranks ``0..n-1`` (rank r drawn with weight
    ``1/(r+1)**s``), scattered onto keys by ``key = (a*r + b) mod n``
    with ``gcd(a, n) = 1``; a pure function of ``(n, s, seed)``."""

    def __init__(self, n_keys: int, s: float, seed: int):
        ranks = np.arange(1, n_keys + 1, dtype=np.float64)
        cdf = np.cumsum(ranks ** -s)
        self._cdf = cdf / cdf[-1]
        self.n_keys = n_keys
        self._rng = random.Random(f"zipf:{seed}")
        a = n_keys - 1 if n_keys > 2 else 1
        for _ in range(64):
            c = self._rng.randrange(2, n_keys) if n_keys > 2 else 1
            if math.gcd(c, n_keys) == 1:
                a = c
                break
        self._a = a
        self._b = self._rng.randrange(n_keys)

    def draw(self) -> int:
        rank = int(np.searchsorted(self._cdf, self._rng.random(), "left"))
        return (self._a * min(rank, self.n_keys - 1) + self._b) % self.n_keys


class OpStream:
    """The seeded sequence of ops a run submits, in submission order.

    Each op is a dict ``{"kind", "key", "value"}``: ``value`` is the
    written value of a write and the FAA addend of an RMW."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        self._n = config["recordcount"]
        self._rules = {k: traffic.get("keys", {}).get(k, "zipf")
                       for k in KINDS}
        used = {self._rules[k] for k, share in traffic["mix"].items()
                if share > 0}
        self._zipf = (ZipfKeys(self._n, config["zipf_s"], seed)
                      if "zipf" in used else None)
        if used & {"home", "own"}:
            self._home_keys = int(config["home_keys"])
            self._per_home = int(config["terminals_per_home"])
            terminals = config["replicas"] * config["sessions_per_replica"]
            homes = -(-terminals // self._per_home)
            if homes * self._home_keys != self._n:
                raise ValueError(
                    f"{terminals} terminals, {self._per_home} to a home of "
                    f"{self._home_keys} keys, need recordcount "
                    f"{homes * self._home_keys}, not {self._n}")
        self._rng = random.Random(f"ops:{seed}")
        self._cum = []
        acc = 0.0
        for k in KINDS:
            acc += traffic["mix"].get(k, 0.0)
            self._cum.append((acc, k))
        self._arg = int(traffic.get("rmw_arg", 1))

    def _key(self, rule: str, terminal: int) -> int:
        if rule == "zipf":
            return self._zipf.draw()
        if rule == "uniform":
            return self._rng.randrange(self._n)
        first = terminal // self._per_home * self._home_keys
        if rule == "home":
            return first + self._rng.randrange(self._home_keys)
        return first + terminal % self._per_home % self._home_keys

    def next(self, terminal: int) -> dict:
        r = self._rng.random()
        kind = next((k for c, k in self._cum if r < c), self._cum[-1][1])
        value = (self._rng.randrange(1, 1 << 30) if kind == "write"
                 else self._arg if kind == "rmw" else 0)
        return {"kind": kind, "key": self._key(self._rules[kind], terminal),
                "value": value}


def arrival_offsets(traffic: dict, seed: int, seconds: float) -> List[float]:
    """Open loop: scheduled arrival offsets (s) into a window of
    ``seconds``; Poisson at ``rate_ops_per_s``."""
    rng = random.Random(f"arrivals:{seed}")
    rate = float(traffic["rate_ops_per_s"])
    out, t = [], 0.0
    while True:
        t += rng.expovariate(rate)
        if t >= seconds:
            return out
        out.append(t)
