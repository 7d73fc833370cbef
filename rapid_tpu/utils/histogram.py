"""Bounded, mergeable log-bucketed latency histogram.

The ``Metrics`` registry originally kept every timing sample in an unbounded
per-name ``List[float]`` — on a long-lived node that list grows forever,
which disqualifies it for production scrapes. This histogram replaces it with
a FIXED bucket schedule: upper bounds grow geometrically by sqrt(2) per
bucket from 0.01 ms, so any sample lands within a factor of sqrt(2) of its
true value, memory is O(NUM_BUCKETS) regardless of sample count, and two
histograms recorded on different nodes (or epochs) merge by bucket-wise
addition — associative and commutative, which is what lets a dashboard fold
per-node snapshots into one cluster-wide quantile (tools/clustertop.py).

The schedule is a module constant shared by every instance: recorders,
mergers, and the Prometheus renderer (utils/exposition.py emits the
``_bucket``/``_sum``/``_count`` triplet from it) all agree on bucket edges
by construction, so a snapshot serialized as sparse ``{bucket_index: count}``
JSON is portable across processes.
"""

from __future__ import annotations

import math
from bisect import bisect_left
from typing import Dict, Iterable, List, Optional

#: Geometric growth per bucket. sqrt(2) bounds any quantile's relative error
#: at ~41% while covering 0.01 ms .. ~9 hours in 64 buckets.
GROWTH = 2.0 ** 0.5

#: Upper bound of the first bucket, in milliseconds.
FIRST_UPPER_MS = 0.01

#: Finite buckets; one extra overflow bucket (index NUM_BUCKETS) plays the
#: Prometheus ``+Inf`` role. Inside it the schedule goes on (``tail_index``),
#: kept sparsely, so a quantile past the last finite bound keeps the same
#: error contract instead of reading the max.
NUM_BUCKETS = 64

#: The fixed schedule: ``UPPER_BOUNDS_MS[i]`` is the inclusive upper bound of
#: bucket i. Values above the last bound land in the overflow bucket.
UPPER_BOUNDS_MS = tuple(FIRST_UPPER_MS * GROWTH**i for i in range(NUM_BUCKETS))


def bucket_index(value_ms: float) -> int:
    """Index of the bucket holding ``value_ms`` (<= its upper bound);
    non-positive values fall into bucket 0, values past the last finite
    bound into the overflow bucket NUM_BUCKETS."""
    if value_ms <= FIRST_UPPER_MS:
        return 0
    return bisect_left(UPPER_BOUNDS_MS, value_ms)


def tail_index(value_ms: float) -> int:
    """For a value in the overflow bucket: the index (>= NUM_BUCKETS) of the
    bucket it would hold on the schedule continued past its last finite
    bound, ``FIRST_UPPER_MS * GROWTH**i``. The logarithm only guesses; the
    two loops make the bounds exact (``bound(i - 1) < value <= bound(i)``)."""
    idx = max(NUM_BUCKETS, math.ceil(math.log(value_ms / FIRST_UPPER_MS, GROWTH)))
    while idx > NUM_BUCKETS and FIRST_UPPER_MS * GROWTH ** (idx - 1) >= value_ms:
        idx -= 1
    while FIRST_UPPER_MS * GROWTH**idx < value_ms:
        idx += 1
    return idx


class LogHistogram:
    """Fixed-schedule log-bucketed histogram of millisecond durations.

    Quantiles come back as the upper bound of the bucket containing the
    requested rank, clamped to the exact recorded max — so for any recorded
    distribution ``true_q <= quantile(q) <= true_q * GROWTH`` (the rank-bound
    property pinned by tests/test_histogram_properties.py). ``merge`` adds
    bucket counts, counts, and sums, and takes the max of maxima: associative
    and commutative over everything except ``last`` (which is a display
    nicety, defined as the most recent operand's last sample).

    The overflow bucket's samples are also counted by :func:`tail_index` in
    a sparse ``{index: count}``, which is what holds the rank bound past the
    last finite bound (a 9-hour sample is no latency; the store under
    ``compile_ms`` and the property tests reach it). It grows by one entry
    per occupied sqrt(2) step, some 2,000 for every finite float.
    """

    __slots__ = ("_counts", "_tail", "count", "sum", "max", "last")

    def __init__(self) -> None:
        self._counts: List[int] = [0] * (NUM_BUCKETS + 1)
        self._tail: Dict[int, int] = {}
        self.count = 0
        self.sum = 0.0
        self.max = 0.0
        self.last = 0.0

    def observe(self, value_ms: float) -> None:
        idx = bucket_index(value_ms)
        self._counts[idx] += 1
        if idx == NUM_BUCKETS and value_ms != math.inf:  # inf has no step; it is the max
            tail = tail_index(value_ms)
            self._tail[tail] = self._tail.get(tail, 0) + 1
        self.count += 1
        self.sum += value_ms
        if value_ms > self.max:
            self.max = value_ms
        self.last = value_ms

    # -- merging -------------------------------------------------------

    def merge(self, other: "LogHistogram") -> "LogHistogram":
        """Fold ``other`` into self (in place); returns self for chaining."""
        for i, c in enumerate(other._counts):
            self._counts[i] += c
        for i, c in other._tail.items():
            self._tail[i] = self._tail.get(i, 0) + c
        self.count += other.count
        self.sum += other.sum
        if other.max > self.max:
            self.max = other.max
        if other.count:
            self.last = other.last
        return self

    @classmethod
    def merged(cls, histograms: Iterable["LogHistogram"]) -> "LogHistogram":
        out = cls()
        for hist in histograms:
            out.merge(hist)
        return out

    # -- quantiles -----------------------------------------------------

    def quantile(self, q: float) -> float:
        """The q-quantile (0 < q <= 1) as the containing bucket's upper
        bound, clamped to the exact max; 0.0 on an empty histogram."""
        if self.count == 0:
            return 0.0
        rank = min(self.count, max(1, math.ceil(q * self.count)))
        cumulative = 0
        for i, c in enumerate(self._counts[:NUM_BUCKETS]):
            cumulative += c
            if cumulative >= rank:
                return min(UPPER_BOUNDS_MS[i], self.max)
        for i in sorted(self._tail):
            cumulative += self._tail[i]
            if cumulative >= rank:
                return min(FIRST_UPPER_MS * GROWTH**i, self.max)
        # An overflow count without its tail (a summary written before the
        # tail existed): all that is known of those samples is the max.
        return self.max

    # -- snapshots -----------------------------------------------------

    def summary(self) -> Dict[str, object]:
        """JSON-ready bounded summary: headline quantiles plus the sparse
        bucket counts (``{index: count}``, string keys for JSON round-trip)
        the Prometheus renderer and cross-node mergers consume. Size is
        O(NUM_BUCKETS) no matter how many samples were recorded."""
        out: Dict[str, object] = {
            "count": self.count,
            "last": round(self.last, 3),
            "p50": round(self.quantile(0.50), 3),
            "p90": round(self.quantile(0.90), 3),
            "p99": round(self.quantile(0.99), 3),
            # Exact: ``from_summary`` restores it, and ``quantile`` clamps
            # to it (a rounded max could read below a recorded sample).
            "max": self.max,
            "sum": round(self.sum, 3),
            "buckets": {str(i): c for i, c in enumerate(self._counts) if c},
        }
        if self._tail:
            out["tail"] = {str(i): c for i, c in sorted(self._tail.items())}
        return out

    @classmethod
    def from_summary(cls, summary: Dict[str, object]) -> "LogHistogram":
        """Rebuild a mergeable histogram from a ``summary()`` dict (e.g. one
        loaded from a telemetry-snapshot JSON file). Tolerates missing keys:
        a legacy timer dict without buckets rebuilds as count-only."""
        out = cls()
        for key, c in (summary.get("buckets") or {}).items():
            idx = int(key)
            if 0 <= idx <= NUM_BUCKETS:
                out._counts[idx] += int(c)
        for key, c in (summary.get("tail") or {}).items():
            out._tail[int(key)] = int(c)
        out.count = int(summary.get("count", 0))
        out.sum = float(summary.get("sum", 0.0))
        out.max = float(summary.get("max", 0.0))
        out.last = float(summary.get("last", 0.0))
        return out

    def cumulative_buckets(self) -> List[tuple]:
        """(upper_bound_ms, cumulative_count) pairs for Prometheus
        ``_bucket`` rendering: every finite bound up to the highest occupied
        bucket, then ``("+Inf", count)``. Cumulative counts make truncating
        the empty tail spec-valid — all omitted bounds equal the total."""
        out: List[tuple] = []
        highest = max((i for i, c in enumerate(self._counts) if c), default=-1)
        cumulative = 0
        for i in range(min(highest, NUM_BUCKETS - 1) + 1):
            cumulative += self._counts[i]
            out.append((UPPER_BOUNDS_MS[i], cumulative))
        out.append(("+Inf", self.count))
        return out


def cumulative_from_summary(summary: Dict[str, object]) -> Optional[List[tuple]]:
    """``cumulative_buckets()`` for a summary dict, or None when the dict
    carries no bucket data (legacy snapshot) — the exposition layer's seam."""
    if "buckets" not in summary:
        return None
    return LogHistogram.from_summary(summary).cumulative_buckets()
