"""The dispatch journal (``utils/engine_telemetry.py``, ``utils/dispatch.py``).

Every closed ``_dispatch`` block is one row: its start, the moment its wait
began (fetching phases only) and its end, under the id of the membership
change it served; every closed change is one row too and one sample of
``engine_change_ms``. One subprocess drives a small ``VirtualCluster``, a
``TenantFleet`` and two streams and prints the rows each left (the module's
compiles stay out of the pytest process); the cases read them. The tags on the
profiler's ``rapid:<phase>`` spans are ``tests/test_spans.py``'s.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from rapid_tpu.utils import dispatch, engine_telemetry

REPO = Path(__file__).resolve().parent.parent

_DRIVE = r"""
import json, math, tracemalloc
import jax
import numpy as np
from rapid_tpu.models.virtual_cluster import VirtualCluster
from rapid_tpu.tenancy.fleet import TenantFleet
from rapid_tpu.serving.stream import FleetWave, StreamDriver, StreamWave
from rapid_tpu.utils import engine_telemetry

def plain(row, names):
    out = {name: row[name].item() for name in row.dtype.names}
    if "phase" in out:
        out["phase"] = names[out["phase"]]
        out["t_wait"] = None if math.isnan(out["t_wait"]) else out["t_wait"]
    return out

class Since:
    # the rows the journal gained, and the phase sums as they stood before
    def __init__(self, driver):
        kept = engine_telemetry.journal_snapshot()
        self.driver = driver
        self.dispatches, self.changes = kept["dispatches_written"], kept["changes_written"]
        self.sums = self.phase_sums()
    def phase_sums(self):
        family = self.driver.metrics.phase_timings.get("engine_dispatch", {})
        return {name: hist.sum for name, hist in family.items()}
    def rows(self):
        kept = engine_telemetry.journal_snapshot()
        n, m = kept["dispatches_written"] - self.dispatches, kept["changes_written"] - self.changes
        timer = self.driver.metrics.timings.get("engine_change")
        return {
            "dispatches": [plain(r, kept["phases"]) for r in kept["dispatches"][len(kept["dispatches"]) - n:]],
            "changes": [plain(r, ()) for r in kept["changes"][len(kept["changes"]) - m:]],
            "sums_before": self.sums, "sums_after": self.phase_sums(),
            "change_samples": 0 if timer is None else timer.count,
            "change_sum_ms": 0.0 if timer is None else timer.sum,
        }

def cluster():
    vc = VirtualCluster.create(28, n_slots=40, k=3, h=3, l=1, cohorts=2, fd_threshold=2)
    vc.assign_cohorts_roundrobin()
    return vc

out = {}

vc = cluster()
since = Since(vc)
vc.sync()                                   # before any change
vc.crash([1]); vc.inject_join_wave([30]); vc.sync()
vc.run_until_membership(28, max_steps=64, max_cuts=4, min_cuts=1)
vc.sync()                                   # after the change closed
out["cluster"] = since.rows()

fleet = TenantFleet.create(3, 24, n_slots=24, k=3, cohorts=2, seeds=[1, 2, 3], knobs=[(3, 1, 2)] * 3)
fleet.sync()
since = Since(fleet)
fleet.stream_crash([(0, 2), (1, 3), (2, 4)])
fleet.run_to_decision(max_steps=32)
out["fleet"] = since.rows()

def never(index, ticket):
    return False

def settled(index, ticket):
    jax.block_until_ready(ticket)  # a probe that always finds the wave done
    return True

for name, probe, depth in (("stream_fetched", never, 2), ("stream_reaped", settled, 4)):
    vc = cluster()
    vc.sync()
    driver = StreamDriver(vc, rounds_per_wave=2, depth=depth, ticket_ready=probe)
    since = Since(vc)
    for i in range(3):
        driver.submit(StreamWave(crash=(5 + i,), join=()))
    driver.drain()
    out[name] = since.rows()

fvc = TenantFleet.create(2, 24, n_slots=24, k=3, cohorts=2, seeds=[4, 5], knobs=[(3, 1, 2)] * 2)
fvc.sync()
driver = StreamDriver(fvc, rounds_per_wave=2, depth=2, ticket_ready=never)
since = Since(fvc)
for i in range(2):
    driver.submit(FleetWave(crash=((0, 3 + i), (1, 7 + i))))
driver.drain()
out["stream_fleet"] = since.rows()

# The ring: 200,000 empty blocks on one driver.
vc = cluster()
collector = engine_telemetry._COLLECTOR
def columns():
    # the ring's parallel arrays: which objects, how many bytes
    return [(id(c), len(c) * c.itemsize) for c in collector.dispatches.columns]
ring_before = columns()
tracemalloc.start()
capacity = engine_telemetry.JOURNAL_CAPACITY
for _ in range(capacity):
    with vc._dispatch("step"):
        pass
held = tracemalloc.get_traced_memory()[0]
for _ in range(200_000 - capacity):
    with vc._dispatch("step"):
        pass
grown = tracemalloc.get_traced_memory()[0] - held
tracemalloc.stop()
kept = engine_telemetry.journal_snapshot()
mine = kept["dispatches"][kept["dispatches"]["driver"] == vc._driver]
out["ring"] = {
    "capacity": capacity, "held": len(kept["dispatches"]), "grown_bytes": grown,
    "same_arrays": ring_before == columns(), "ring_bytes": sum(n for _, n in ring_before),
    "first_seq": int(mine["seq"][0]), "last_seq": int(mine["seq"][-1]), "rows_of_driver": len(mine),
    "seq_steps": sorted(set(np.diff(mine["seq"]).tolist())),
    "ends_rise": bool((np.diff(kept["dispatches"]["t_end"]) >= 0).all()),
    "dispatched": int(vc.metrics.counters["engine_dispatches"]),
}
print("JOURNAL " + json.dumps(out))
"""

DRIVES = ("cluster", "fleet", "stream_fetched", "stream_reaped", "stream_fleet")


@pytest.fixture(scope="module")
def journal():
    done = subprocess.run(
        [sys.executable, "-c", _DRIVE], cwd=str(REPO),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-4000:]
    line = next(l for l in done.stdout.splitlines() if l.startswith("JOURNAL "))
    return json.loads(line[len("JOURNAL "):])


def _by_phase(rows, phase):
    return [row for row in rows if row["phase"] == phase]


def _union_s(rows):
    covered, reach = 0.0, float("-inf")
    for row in sorted(rows, key=lambda r: r["t_start"]):
        covered += max(0.0, row["t_end"] - max(row["t_start"], reach))
        reach = max(reach, row["t_end"])
    return covered


def test_a_commit_leaves_rows_with_rising_seq_and_one_shared_change(journal):
    rows = journal["cluster"]["dispatches"]
    assert [row["phase"] for row in rows] == [
        "sync", "inject_crash", "inject_join_admit", "inject_join_place", "sync",
        "run_until_membership", "sync",
    ]
    seqs = [row["seq"] for row in rows]
    assert seqs == list(range(seqs[0], seqs[0] + len(rows)))
    assert len({row["driver"] for row in rows}) == 1 and rows[0]["driver"] > 0
    served = {row["change"] for row in rows[1:6]}
    assert len(served) == 1 and served.pop() > 0
    ends = [row["t_end"] for row in rows]
    assert ends == sorted(ends)


@pytest.mark.parametrize("phase", ["inject_join_admit", "sync", "run_until_membership"])
def test_a_fetching_phase_is_stamped_between_its_start_and_its_end(journal, phase):
    rows = _by_phase(journal["cluster"]["dispatches"], phase)
    assert rows
    for row in rows:
        assert row["t_wait"] is not None
        assert row["t_start"] <= row["t_wait"] <= row["t_end"]


@pytest.mark.parametrize("drive, phase", [
    ("cluster", "inject_crash"), ("cluster", "inject_join_place"),
    ("fleet", "inject_crash"), ("stream_fetched", "stream_enqueue"),
])
def test_a_phase_that_only_enqueues_has_no_wait_mark(journal, drive, phase):
    rows = _by_phase(journal[drive]["dispatches"], phase)
    assert rows and all(row["t_wait"] is None for row in rows)


@pytest.mark.parametrize("which", ["before", "after"])
def test_a_call_outside_any_change_carries_zero(journal, which):
    rows = journal["cluster"]["dispatches"]
    row = rows[0] if which == "before" else rows[-1]
    assert row["phase"] == "sync" and row["change"] == 0


@pytest.mark.parametrize("drive, requests", [
    ("cluster", 1), ("fleet", 1), ("stream_fetched", 3), ("stream_reaped", 3), ("stream_fleet", 2),
])
def test_every_request_closes_exactly_one_change_no_shorter_than_its_dispatches(journal, drive, requests):
    rows, changes = journal[drive]["dispatches"], journal[drive]["changes"]
    assert len(changes) == requests
    assert len({change["change"] for change in changes}) == requests
    for change in changes:
        mine = [row for row in rows if row["change"] == change["change"]]
        assert mine and {row["driver"] for row in mine} == {change["driver"]}
        assert change["seq_first"] == mine[0]["seq"] and change["seq_last"] == mine[-1]["seq"]
        assert change["dispatch_s"] == pytest.approx(sum(r["t_end"] - r["t_start"] for r in mine), rel=1e-9)
        pending = change["t_close"] - change["t_open"]
        assert change["t_open"] <= mine[0]["t_start"] and mine[-1]["t_end"] <= change["t_close"]
        assert pending >= _union_s(mine) > 0  # so no unphased part is negative
    # and nothing else carries an id: every non-zero id on a row closed
    assert {row["change"] for row in rows} - {0} == {change["change"] for change in changes}


@pytest.mark.parametrize("drive", DRIVES)
def test_engine_change_ms_takes_one_sample_a_closed_change(journal, drive):
    record = journal[drive]
    assert record["change_samples"] == len(record["changes"])
    pending_ms = sum((c["t_close"] - c["t_open"]) * 1e3 for c in record["changes"])
    assert record["change_sum_ms"] == pytest.approx(pending_ms, rel=1e-9)


@pytest.mark.parametrize("drive, phase", [("cluster", "run_until_membership"), ("fleet", "fleet_decision")])
def test_a_deciding_row_reports_its_rounds_and_the_bytes_it_fetched(journal, drive, phase):
    (row,) = _by_phase(journal[drive]["dispatches"], phase)
    assert row["rounds"] >= 1 and row["bytes"] >= 4 and row["compiles"] >= 0 and row["gc_s"] >= 0.0
    others = [r for r in journal[drive]["dispatches"] if r["phase"] not in dispatch.DECIDING_PHASES]
    assert all(r["rounds"] == 0 for r in others)


@pytest.mark.parametrize("path", ["fetched", "reaped"])
def test_a_stream_wave_is_a_change_whichever_way_it_is_retired(journal, path):
    record = journal["stream_" + path]
    rows = record["dispatches"]
    tails = []
    for change in record["changes"]:
        phases = [row["phase"] for row in rows if row["change"] == change["change"]]
        assert phases[:3] == ["inject_crash", "stream_enqueue", "stream_enqueue"]
        tails.append(phases[3:])
    # A wave the blocking fetch retires ends in that fetch. One the probe finds
    # done is closed with no dispatch after its last enqueue; the drain's sweep
    # fetches the one wave no later submit was there to reap.
    assert tails == ([["stream_fetch"]] * 3 if path == "fetched" else [[], [], ["stream_fetch"]])
    fetches = _by_phase(rows, "stream_fetch")
    assert all(row["t_wait"] is not None for row in fetches)
    assert fetches[-1]["change"] == 0  # the drain's epoch fetch serves no wave


@pytest.mark.parametrize("drive", DRIVES)
def test_the_rows_durations_add_up_to_the_histograms_sums(journal, drive):
    """The same two clock reads feed a row and its phase's histogram, so the
    journal reproduces every ``engine_dispatch`` sum, and ``cum_ms`` is that
    sum as it stood when the row closed."""
    record = journal[drive]
    for phase, after in record["sums_after"].items():
        rows = _by_phase(record["dispatches"], phase)
        moved = after - record["sums_before"].get(phase, 0.0)
        assert sum((r["t_end"] - r["t_start"]) * 1e3 for r in rows) == pytest.approx(moved, rel=1e-6, abs=1e-9)
        if rows:
            assert rows[-1]["cum_ms"] == after
            assert [r["cum_ms"] for r in rows] == sorted(r["cum_ms"] for r in rows)


@pytest.mark.parametrize("what", ["memory", "order"])
def test_the_ring_overwrites_at_capacity(journal, what):
    ring = journal["ring"]
    if what == "memory":
        assert ring["same_arrays"] and ring["held"] == ring["capacity"] == engine_telemetry.JOURNAL_CAPACITY
        assert ring["ring_bytes"] == ring["capacity"] * engine_telemetry.DISPATCH_RECORD.itemsize
        # what 134,464 more dispatches left behind: nothing a row long
        assert ring["grown_bytes"] < 64 * 1024, ring["grown_bytes"]
        return
    # the newest 65,536, oldest first: the driver's last operations, in order
    assert ring["rows_of_driver"] == ring["capacity"]
    assert ring["last_seq"] == ring["dispatched"]
    assert ring["first_seq"] == ring["dispatched"] - ring["capacity"] + 1
    assert ring["seq_steps"] == [1] and ring["ends_rise"]


@pytest.mark.parametrize("subset", ["INJECTING_PHASES", "DECIDING_PHASES"])
def test_an_unregistered_name_in_a_phase_subset_fails_the_import(subset):
    phases = getattr(dispatch, subset)
    assert phases and phases <= dispatch.ENGINE_DISPATCH_PHASES
    assert dispatch._registered_phases(subset, phases) == phases
    with pytest.raises(ValueError, match=f"{subset} names unregistered engine dispatch phases"):
        dispatch._registered_phases(subset, {*phases, "inject_crsh"})
    assert not dispatch.INJECTING_PHASES & dispatch.DECIDING_PHASES
    # the journal's rows name their phase by its place in the sorted vocabulary
    assert engine_telemetry.journal_snapshot()["phases"] == tuple(sorted(dispatch.ENGINE_DISPATCH_PHASES))
    assert len(dispatch.ENGINE_DISPATCH_PHASES) <= 256  # ``phase`` is a uint8


def test_the_record_types_name_the_fields_the_readers_take():
    assert engine_telemetry.DISPATCH_RECORD.names == (
        "phase", "driver", "seq", "change", "t_start", "t_wait", "t_end",
        "compiles", "gc_s", "bytes", "rounds", "cum_ms",
    )
    assert engine_telemetry.CHANGE_RECORD.names == (
        "change", "driver", "t_open", "t_close", "seq_first", "seq_last", "dispatch_s",
    )
    fresh = engine_telemetry._CompileCollector().journal_snapshot()
    assert len(fresh["dispatches"]) == 0 and fresh["dispatches"].dtype == engine_telemetry.DISPATCH_RECORD
    assert len(fresh["changes"]) == 0 and fresh["changes_written"] == 0
