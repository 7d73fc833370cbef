"""``cluster-100k-zoned.partition`` rehearsed on the CPU at a few thousand
members, and the data checks that hold the cell to its source's sizes. The cell
keeps its name, traffic file, generator, target, readers and controls; this
file swaps the configuration's size for a small one in a throw-away checkout of
its own (``tiny.py`` knows the cells of the first benchmark only). Run with
``python -m pytest benchmarks/tests/test_cluster_100k_zoned_cell.py -q`` from
the root.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmarks import consensus_model
from benchmarks.tests import tiny

CELL = "cluster-100k-zoned.partition"
CONFIG_FILE = "benchmarks/configs/cluster-100k-zoned.json"
BENCH = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8"))
#: 4,000 members in 4,100 slots: 100 crash a step, a rack of 200, 64 cohorts in 8 zones.
TINY = {"members": 4000, "slots": 4100}
END_TO_END = {"commit_ms_p50", "setup_s"}
PER_LAYER = {
    "host_blocked_share.commit", "d2h_bytes_per_commit.commit", "round_us.commit",
    "rounds_per_commit.commit", "device_idle_share.commit", "delivery_kernel_us", "delivery_roofline",
    "inject_crash_ms.commit", "sync_ms.commit", "state_build_s", "warmup_programs",
    "compiles_in_window", "setup_trace_s", "setup_lower_s", "setup_load_s", "setup_create_s",
    "inject_partition_ms.commit", "classic_rounds_per_commit",
}
#: What a CPU run has nothing to read for: the Mosaic kernel runs on the chip only.
CHIP_ONLY = {"delivery_kernel_us", "delivery_roofline"}


def held(path: str) -> dict:
    with open(os.path.join(tiny.REPO, path), encoding="utf-8") as handle:
        return json.load(handle)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    where = tiny.checkout(str(tmp_path_factory.mktemp("bench_zoned")))
    path = os.path.join(where, CONFIG_FILE)
    with open(path, encoding="utf-8") as handle:
        config = json.load(handle)
    config.update(TINY)
    with open(path, "w", encoding="utf-8") as handle:
        json.dump(config, handle)
    return where


# -- the data: the cell is the source's deployment, nothing cut ----------------


def test_the_cell_and_its_configuration_are_the_sources():
    entry = next(c for c in BENCH["configs"] if c["name"] == "cluster-100k-zoned")
    config = held(entry["file"])
    assert entry["file"] == CONFIG_FILE and entry["reduced"] == [] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert (config["members"], config["slots"], config["cohorts"], config["zones"]) == (100_000, 102_500, 64, 8)
    assert (config["k"], config["h"], config["l"]) == (10, 9, 4)
    assert (config["fd_threshold"], config["fd_stagger_rounds"], config["delivery_spread"]) == (3, 3, 2)
    assert (config["concurrent_coordinators"], config["fallback_rounds"]) == (2, 8)
    assert config["deployment"] == "cluster_partition" and config["use_pallas"] is True
    assert set(config["assumed"]) == {
        "slots", "cohorts", "zones", "delivery_spread", "fd_threshold", "fd_stagger_rounds",
        "concurrent_coordinators", "fallback_rounds"}
    assert any("decided by the classic round" in line for line in config["guarantees"])
    assert any("no fast quorum" in line for line in config["guarantees"])
    # the engine's round programs are churn5's: every shape the two configurations share
    churn = held("benchmarks/configs/cluster-100k.json")
    shared = ("members", "slots", "k", "h", "l", "cohorts", "fd_threshold", "fd_stagger_rounds",
              "delivery_spread", "concurrent_coordinators", "use_pallas", "pallas_lanes")
    assert all(config[key] == churn[key] for key in shared)
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "cluster-100k-zoned" and len(cell["why"]) <= 200
    traffic = held(f"benchmarks/traffic/{cell['traffic']}.json")
    assert traffic["kind"] == "partition" and traffic["resolve"] == "until_membership"
    assert (traffic["crash_share"], traffic["rack_share"], traffic["deaf_zones"]) == (0.025, 0.05, 3)
    assert (traffic["plan_cycle"], traffic["arrival_seed"]) == (16, 7)
    # the traffic's arithmetic: 37.5 % deaf leave 60,937 of 97,500 voters for a quorum of 75,001
    assert consensus_model.fast_quorum(100_000) == 75_001 and consensus_model.majority(100_000) == 50_001
    assert (100_000 - 2_500) * (64 - 24) // 64 < 75_001 <= 100_000 - 2_500


def test_the_cell_is_on_the_lists_of_the_metrics_it_reports():
    listed = {
        group: {m["name"] for m in BENCH[group] if CELL in m.get("workloads", [CELL])}
        for group in ("end_to_end", "per_layer")
    }
    assert listed["end_to_end"] == END_TO_END
    assert listed["per_layer"] == PER_LAYER
    for name, source in (("inject_partition_ms.commit", "program_span"),
                         ("classic_rounds_per_commit", "program_counter")):
        metric = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert metric["moves"] == "commit_ms_p50" and metric["workloads"] == [CELL]
        assert metric["source"] == source


# -- the plain reference, by itself -----------------------------------------------


def test_the_reference_counts_quorums_and_tallies_as_the_papers_rule():
    assert [consensus_model.fast_quorum(n) for n in (4, 5, 100, 1000)] == [4, 4, 76, 751]
    # ten rings; the subject 0 is watched by 1..10, of which 1 and 2 crashed too
    observers = np.full((10, 40), -1)
    observers[:, 0] = np.arange(1, 11)
    observers[:, 1] = observers[:, 2] = np.arange(20, 30)
    crashed = np.zeros(40, dtype=bool)
    crashed[[0, 1, 2]] = True
    nobody = np.zeros(40, dtype=bool)
    tally = consensus_model.reports(observers, crashed, nobody, low=4)
    assert tally[:3].tolist() == [10, 10, 10]  # eight direct reports and two implicit ones
    deaf_to = nobody.copy()
    deaf_to[[3, 4]] = True  # two healthy observers of the subject 0 go unheard
    tally = consensus_model.reports(observers, crashed, deaf_to, low=4)
    assert tally[0] == 8 and consensus_model.proposal(tally, 9, 4) is None  # between the watermarks
    deaf_to[[5, 6, 7, 8, 9]] = True  # seven of ten unheard: one direct report, under L
    tally = consensus_model.reports(observers, crashed, deaf_to, low=4)
    assert tally[0] == 1 and consensus_model.proposal(tally, 9, 4).nonzero()[0].tolist() == [1, 2]


# -- the cell, small, through the harness ---------------------------------------


def test_the_cell_runs_end_to_end_and_is_correct(checkout):
    done = tiny.run_cell(checkout, CELL, seed=2**31 + 77, seconds=1.0)
    result = tiny.result_of(done)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 16 and result["attempted"] % 16 == 0  # whole cycles
    assert set(result["metrics"]) == END_TO_END
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert "the plain reference expects the classic path in every one" in done.stdout
    for name in ("healthy_evicted", "crashed_in_view", "unresolved", "cut_sizes_unaccounted",
                 "view_changes_out_of_range"):
        assert f"check {name}: value=0 limit=0" in done.stdout


def test_a_traced_run_reports_the_cells_per_layer_metrics(checkout):
    done = tiny.run_cell(checkout, CELL, seed=5, seconds=1.0, trace=1)
    result = tiny.result_of(done)
    assert result["correct"] is True
    assert set(result["metrics"]) == PER_LAYER - CHIP_ONLY
    metrics = {name: entry["value"] for name, entry in result["metrics"].items()}
    # 4 the sync's checksum, 28 the wave's observation, 12 the three counts of the path
    assert metrics["d2h_bytes_per_commit.commit"] == 44
    assert metrics["compiles_in_window"] == 0
    assert metrics["inject_partition_ms.commit"] > 0 and metrics["inject_crash_ms.commit"] > 0
    assert metrics["classic_rounds_per_commit"] == 1.0
    assert metrics["rounds_per_commit.commit"] == 15.0
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result


def test_the_readers_read_nothing_from_a_program_without_the_seam():
    from benchmarks.metrics import classic_rounds_per_commit, inject_partition_ms

    recorded = {
        "counters_before": {"d2h_bytes": 0, "dispatch_ms": {"sync": 1.0}},
        "counters_after": {"d2h_bytes": 9, "dispatch_ms": {"sync": 5.0}},
        "commit_ms": [1.0, 2.0], "attempted": 2,
    }
    assert classic_rounds_per_commit.read(recorded) is None and inject_partition_ms.read(recorded) is None
    recorded["counters_before"].update(consensus={"classic_rounds": 3, "classic_decisions": 3, "fast_decisions": 0})
    recorded["counters_after"].update(consensus={"classic_rounds": 6, "classic_decisions": 5, "fast_decisions": 0})
    recorded["counters_after"]["dispatch_ms"]["inject_partition"] = 3.0
    assert classic_rounds_per_commit.read(recorded) == 1.5 and inject_partition_ms.read(recorded) == 1.5


# -- the controls ---------------------------------------------------------------


@pytest.mark.parametrize("script,fault,broken", [
    ("benchmarks/control_partition.py", "lose_partition", "view_changes_out_of_range"),
    ("benchmarks/control_partition.py", "never_fall_back", "unresolved"),
    ("benchmarks/control.py", "evict_healthy", "healthy_evicted"),
    ("benchmarks/control.py", "lose_crash", "crashed_in_view"),
])
def test_the_controls_come_out_not_correct(checkout, script, fault, broken):
    done = tiny.run_cell(checkout, CELL, seconds=0.2, script=script, extra=("--fault", fault))
    result = tiny.result_of(done)
    assert result["correct"] is False and result["failed"] == result["attempted"] > 0
    assert f"check {broken}: value=1 limit=0" in done.stdout
    if fault == "lose_partition":  # the fast round decided: everything but the path holds
        assert "check unresolved: value=0 limit=0" in done.stdout
        assert "check healthy_evicted: value=0 limit=0" in done.stdout
