"""Traffic generators: what a cell's mix file asks for, made from the seed.

Every seed gets the same set of sizes and arrival gaps, in another order:
lengths are the mix's lognormal at evenly spaced quantiles, clipped, and gaps
are the exponential's quantiles.  The order is stratified, so that every run
of ``STRATA`` consecutive requests holds one of each size stratum, and the part
of the set a window reaches is alike from seed to seed.  So two seeds offer
the same work, and a spread between runs is the system's and not the draw's.
A mix that gives ``schedule_seed`` fixes the order too: every run replays one
schedule of sizes and arrival times, and the run's seed draws only the prompt
tokens (an open loop near its knee queues differently under each order, and
a closed loop's first tokens wait behind other prefills, so the tail would
follow the order more than the system).  Prompt tokens are drawn uniformly
from the vocabulary.

Two loops, both on the wall clock:

* ``open``: Poisson arrivals at the mix's ``rate`` (requests per second),
  due whether or not the server keeps up.  Each request is timed from when
  it was due, and how late the generator submitted it is reported.
* ``closed``: ``clients`` clients that each send their next request the moment
  the previous one finished (no think time).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream for (seed, purpose); any int seed works."""
    return np.random.default_rng([stream, seed % 2**64])


def lognormal_set(spec: dict, n: int) -> np.ndarray:
    """n lengths: the lognormal(median, sigma) at quantiles (i + 0.5) / n,
    clipped to [min, max]; in ascending order."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


STRATA = 16


def stratified_order(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``values`` reordered so that each block of STRATA consecutive entries
    takes one from each of STRATA equal slices of the sorted values."""
    v = np.sort(values)
    n = len(v)
    strata = [rng.permutation(v[i * n // STRATA : (i + 1) * n // STRATA]) for i in range(STRATA)]
    out = []
    for r in range(max(len(s) for s in strata)):
        for i in rng.permutation(STRATA):
            if r < len(strata[i]):
                out.append(strata[i][r])
    return np.asarray(out)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """n inter-arrival gaps: Exp(rate) at quantiles (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""

    idx: int
    prompt_len: int
    out_len: int
    tokens: np.ndarray  # (prompt_len,) int32
    due: float = 0.0  # seconds after the window opened


class Generator:
    """Hands out requests as they come due.

    ``due(now)`` returns the requests due by ``now`` (window seconds) that
    were not handed out yet; ``finished(now, n)`` tells a closed loop that n
    of its clients got their answers at ``now``.
    """

    def __init__(self, mix: dict, vocab: int, seed: int, seconds: float):
        self.mix = mix
        self.loop = mix["loop"]
        if self.loop == "open":
            n = int(math.ceil(mix["rate"] * seconds * 1.2)) + 8
        elif self.loop == "closed":
            n = mix["pool"]
        else:
            raise ValueError(f"unknown loop {self.loop!r}")
        order = rng_for(mix.get("schedule_seed", seed), 1)
        prompts = stratified_order(lognormal_set(mix["prompt"], n), order)
        outs = stratified_order(lognormal_set(mix["output"], n), order)
        tok_rng = rng_for(seed, 2)
        self.items = [
            Item(i, int(p), int(o), tok_rng.integers(0, vocab, int(p), dtype=np.int32))
            for i, (p, o) in enumerate(zip(prompts, outs))
        ]
        if self.loop == "open":
            gaps = stratified_order(exponential_gaps(mix["rate"], n), order)
            for it, t in zip(self.items, np.cumsum(gaps)):
                it.due = float(t)
        self._next = 0
        # closed loop: window times at which a client is free to send
        self._ready_at: list[float] = [0.0] * mix.get("clients", 0)

    def max_total_len(self) -> int:
        """Longest prompt + output any request of this mix can ask for."""
        return int(self.mix["prompt"]["max"] + self.mix["output"]["max"])

    def next_due(self) -> float | None:
        """Window time of the next request not yet handed out (None: a closed
        loop with no client free, or nothing left)."""
        if self._next >= len(self.items):
            return None
        if self.loop == "open":
            return self.items[self._next].due
        return min(self._ready_at) if self._ready_at else None

    def due(self, now: float) -> list[Item]:
        out = []
        if self.loop == "open":
            while self._next < len(self.items) and self.items[self._next].due <= now:
                out.append(self.items[self._next])
                self._next += 1
            return out
        keep = []
        for t in self._ready_at:
            if t <= now and self._next < len(self.items):
                it = self.items[self._next]
                it.due = t
                out.append(it)
                self._next += 1
            else:
                keep.append(t)
        self._ready_at = keep
        return out

    def finished(self, now: float, n: int) -> None:
        if self.loop == "closed":
            self._ready_at.extend([now] * n)
