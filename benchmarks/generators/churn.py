"""Seeded Poisson churn over the engine's slot table.

Copies of ``PoissonChurn`` and ``FleetPoissonChurn`` from the program's
``rapid_tpu/serving/stream.py``, kept here so that a later change to the
program cannot move the traffic. The draws are the originals' (a Poisson
count per wave or per tenant and wave, joins while fresh slots remain and
crashes of standing original members otherwise, fresh slots never reused)
with two changes. The cluster's coin between joins and crashes is tossed per
wave, not per event (see ``PoissonChurn``). And the sizes come
from one stream and the victims from another. The sizes of one cycle of waves
are drawn once from the traffic file's ``arrival_seed``; the run's seed
shuffles the waves of each cycle, relabels the tenants and draws the victims.
So every seed offers the same arrivals in another order, and a window of
whole cycles holds the same work whatever the seed.

Waves are plain (crash, join) arrays of (tenant, slot) pairs; the stream
generator turns them into the program's wave types.
"""

from __future__ import annotations

from collections import deque

import numpy as np

NO_PAIRS = np.zeros((0, 2), dtype=np.int32)


def _pairs(tenant_slot) -> np.ndarray:
    return np.asarray(tenant_slot, dtype=np.int32).reshape(-1, 2)


class PoissonChurn:
    """One cluster: ``rate`` events a wave; a wave is all joins with
    probability ``join_fraction`` and all crashes otherwise.

    The original tosses the coin per event. A wave that mixes a few crashes
    with joins makes many cohorts propose the joins alone while the crashes
    are still in flux; when more than a quarter do, the fast round cannot
    decide, the classic fallback waits out its eight rounds and the cut
    spills into the next wave. Whether that happens follows the victims, so
    the number of cuts in a window followed the seed (203 to 208 in 208
    waves, PR 24). Unmixed waves commit inside their own eight rounds."""

    def __init__(self, n_members, n_slots, rate, join_fraction, cycle_waves,
                 arrival_seed, seed_sequence):
        if rate <= 0 or not 0.0 <= join_fraction <= 1.0 or not 0 < n_members <= n_slots:
            raise ValueError("need rate > 0, join_fraction in [0, 1], 0 < members <= slots")
        arrivals = np.random.default_rng(arrival_seed)
        #: per wave of the cycle: (events, True = a wave of joins).
        self._cycle = [
            (int(arrivals.poisson(rate)), bool(arrivals.random() < join_fraction))
            for _ in range(cycle_waves)
        ]
        self._rng = np.random.default_rng(seed_sequence)
        self._live = list(range(n_members))
        self._fresh = deque(range(n_members, n_slots))

    def cycle(self):
        """The waves of one cycle, in this run's order."""
        for w in self._rng.permutation(len(self._cycle)):
            events, joins = self._cycle[w]
            crash, join = [], []
            for _ in range(events):
                if joins and self._fresh:
                    join.append((0, self._fresh.popleft()))
                elif self._live:
                    victim = int(self._rng.integers(len(self._live)))
                    crash.append((0, self._live.pop(victim)))
            yield _pairs(crash), _pairs(join)


class FleetPoissonChurn:
    """``tenants`` independent clusters: ``rate`` crashes a tenant a wave."""

    def __init__(self, tenants, n_members, rate, cycle_waves, arrival_seed, seed_sequence):
        if tenants <= 0 or rate <= 0:
            raise ValueError("need tenants > 0 and rate > 0")
        arrivals = np.random.default_rng(arrival_seed)
        self._cycle = arrivals.poisson(rate, size=(cycle_waves, tenants))
        self._rng = np.random.default_rng(seed_sequence)
        self._live = [list(range(n_members)) for _ in range(tenants)]

    def cycle(self):
        for w in self._rng.permutation(len(self._cycle)):
            pairs = []
            relabel = self._rng.permutation(self._cycle.shape[1])
            for column, count in enumerate(self._cycle[w]):
                live = self._live[relabel[column]]
                for _ in range(min(int(count), len(live))):
                    victim = int(self._rng.integers(len(live)))
                    pairs.append((relabel[column], live.pop(victim)))
            yield _pairs(pairs), NO_PAIRS
