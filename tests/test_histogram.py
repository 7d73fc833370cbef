"""Bounded log-bucketed histogram (utils/histogram.py) and the Metrics
registry built on it: the memory-boundedness acceptance claim (1M samples ->
O(buckets) snapshot), bucket-schedule edges, quantile semantics, merge, the
summary/from_summary round trip, and the injected-clock Metrics surface.
Property-based depth (merge associativity/commutativity, quantile rank
bounds, conservation) lives in tests/test_histogram_properties.py.
"""

import json

import pytest

from rapid_tpu.utils.histogram import (
    FIRST_UPPER_MS,
    GROWTH,
    NUM_BUCKETS,
    UPPER_BOUNDS_MS,
    LogHistogram,
    bucket_index,
    cumulative_from_summary,
    tail_index,
)
from rapid_tpu.utils.metrics import Metrics


def test_bucket_schedule_is_fixed_and_monotone():
    assert len(UPPER_BOUNDS_MS) == NUM_BUCKETS
    assert UPPER_BOUNDS_MS[0] == FIRST_UPPER_MS
    for lo, hi in zip(UPPER_BOUNDS_MS, UPPER_BOUNDS_MS[1:]):
        assert hi == pytest.approx(lo * GROWTH)


def test_bucket_index_edges():
    assert bucket_index(-1.0) == 0
    assert bucket_index(0.0) == 0
    assert bucket_index(FIRST_UPPER_MS) == 0  # upper bounds are inclusive
    assert bucket_index(FIRST_UPPER_MS * 1.0001) == 1
    for i in (0, 7, NUM_BUCKETS - 1):
        assert bucket_index(UPPER_BOUNDS_MS[i]) == i
    assert bucket_index(UPPER_BOUNDS_MS[-1] * 2) == NUM_BUCKETS  # overflow


def test_quantiles_track_samples_within_one_bucket():
    hist = LogHistogram()
    samples = [1.0, 2.0, 3.0, 4.0, 100.0]
    for s in samples:
        hist.observe(s)
    assert hist.count == 5
    assert hist.sum == pytest.approx(sum(samples))
    assert hist.max == 100.0
    assert hist.last == 100.0
    # Within GROWTH of the true order statistic, never below it.
    assert 3.0 <= hist.quantile(0.5) <= 3.0 * GROWTH
    assert hist.quantile(0.99) == 100.0  # clamped to the exact max
    assert hist.quantile(1.0) == 100.0
    assert LogHistogram().quantile(0.5) == 0.0


def test_overflow_bucket_keeps_the_rank_bound_and_round_trips():
    # Past the last finite bound the schedule goes on, sparsely: a quantile
    # there is still within GROWTH of the order statistic, not the max.
    last = UPPER_BOUNDS_MS[-1]
    samples = [last * 1.01, last * 1.9, last * 30, 1e9]
    hist = LogHistogram()
    for s in samples:
        hist.observe(s)
    assert hist._counts[NUM_BUCKETS] == 4 and sum(hist._tail.values()) == 4
    for q, true_q in ((0.25, samples[0]), (0.5, samples[1]), (0.75, samples[2]), (1.0, samples[3])):
        assert true_q <= hist.quantile(q) <= true_q * GROWTH * (1 + 1e-12)
    assert tail_index(last * 1.01) == NUM_BUCKETS
    assert tail_index(FIRST_UPPER_MS * GROWTH**70) in (70, 71)  # an exact bound, either side of rounding
    merged = LogHistogram().merge(hist).merge(hist)
    assert merged.quantile(0.5) == hist.quantile(0.5) and merged.count == 8
    back = LogHistogram.from_summary(json.loads(json.dumps(hist.summary())))
    assert back._tail == hist._tail and back.max == hist.max
    assert [back.quantile(q) for q in (0.25, 0.5, 0.75)] == [hist.quantile(q) for q in (0.25, 0.5, 0.75)]
    # Prometheus still sees one +Inf bucket, and a summary written before the
    # tail existed reads the max there, as it always did.
    assert hist.cumulative_buckets()[-1] == ("+Inf", 4)
    legacy = hist.summary()
    del legacy["tail"]
    assert LogHistogram.from_summary(legacy).quantile(0.25) == hist.max
    # The max is kept exact (1.0625 once came back as 1.062), and an infinite
    # sample is the max and raises nothing.
    one = LogHistogram()
    one.observe(1.0625)
    assert LogHistogram.from_summary(one.summary()).max == 1.0625
    one.observe(float("inf"))
    assert one.quantile(1.0) == float("inf") and one._tail == {}
    assert "tail" not in one.summary()


def test_merge_adds_counts_and_keeps_max():
    a, b = LogHistogram(), LogHistogram()
    for v in (1.0, 2.0):
        a.observe(v)
    for v in (3.0, 500.0):
        b.observe(v)
    a.merge(b)
    assert a.count == 4
    assert a.sum == pytest.approx(506.0)
    assert a.max == 500.0
    merged = LogHistogram.merged([LogHistogram(), a, LogHistogram()])
    assert merged.count == 4 and merged.max == 500.0


def test_summary_round_trips_through_json():
    hist = LogHistogram()
    for v in (0.2, 5.0, 5.0, 70.0):
        hist.observe(v)
    summary = json.loads(json.dumps(hist.summary()))
    back = LogHistogram.from_summary(summary)
    assert back.count == hist.count
    assert back.sum == pytest.approx(hist.sum)
    assert back.max == hist.max
    for q in (0.5, 0.9, 0.99):
        assert back.quantile(q) == hist.quantile(q)


def test_cumulative_buckets_end_at_total_and_inf():
    hist = LogHistogram()
    for v in (1.0, 2.0, 2.0):
        hist.observe(v)
    buckets = hist.cumulative_buckets()
    assert buckets[-1] == ("+Inf", 3)
    counts = [c for _, c in buckets]
    assert counts == sorted(counts)  # cumulative
    assert cumulative_from_summary({"count": 1}) is None  # legacy dict


def test_metrics_snapshot_memory_is_bounded_at_one_million_samples():
    """The acceptance claim: recording 1M samples into ONE timer yields an
    O(buckets) snapshot — bounded bucket count and a small serialized form,
    where the old per-name List[float] held 1M floats."""
    metrics = Metrics()
    for i in range(1_000_000):
        metrics.record_ms("convergence", float(i % 1000))
    summary = metrics.summary()["convergence_ms"]
    assert summary["count"] == 1_000_000
    assert len(summary["buckets"]) <= NUM_BUCKETS + 1
    assert len(json.dumps(summary)) < 4096
    assert summary["max"] == 999.0
    assert 500.0 <= summary["p50"] <= 500.0 * GROWTH


def test_metrics_uses_injected_clock_for_timer_and_mark():
    now = [1000.0]
    metrics = Metrics(now_ms=lambda: now[0])
    with metrics.timer("step"):
        now[0] += 250.0
    assert metrics.summary()["step_ms"]["last"] == 250.0
    metrics.mark("epoch")
    now[0] += 40.0
    assert metrics.elapsed_since_ms("epoch") == 40.0
    assert metrics.has_mark("epoch")
    metrics.clear_mark("epoch")
    assert not metrics.has_mark("epoch")
    assert metrics.elapsed_since_ms("epoch") == 0.0


def test_metrics_phase_family_summary_shape():
    metrics = Metrics(now_ms=lambda: 0.0)
    metrics.record_ms("view_change_phase", 5.0, phase="detection")
    metrics.record_ms("view_change_phase", 9.0, phase="agreement/fast")
    summary = metrics.summary()["view_change_phase_ms"]
    assert set(summary) == {"detection", "agreement/fast"}
    assert summary["detection"]["count"] == 1
    # Family entries are phase->histogram dicts (no top-level "count"):
    # that shape difference is how the exposition layer tells a labeled
    # family from a plain timer.
    assert "count" not in summary
