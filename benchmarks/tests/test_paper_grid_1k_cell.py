"""``paper-grid-1k.crashF`` rehearsed on the CPU at one repetition of the grid
and a quarter of the members, and the data checks that hold the cell to its
source's shapes. The cell keeps its name, traffic file, generator, target,
reference, readers and controls; this file swaps the configuration's size for
a small one in a throw-away checkout of its own (``tiny.py`` knows the cells
of the first benchmark only). Run with
``python -m pytest benchmarks/tests/test_paper_grid_1k_cell.py -q`` from the root.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmarks import detector_model
from benchmarks.tests import tiny

CELL = "paper-grid-1k.crashF"
CONFIG_FILE = "benchmarks/configs/paper-grid-1k.json"
BENCH = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8"))
#: the whole grid once: 64 tenants of 250 members, 8 cohorts.
TINY = {"members": 250, "slots": 250, "repetitions": 1, "tenants": 64}
END_TO_END = {"view_changes_per_s", "setup_s"}
OWN = {"fleet_classic_round_share", "tenant_classic_share", "dissent_per_step"}
PER_LAYER = OWN | {
    "host_blocked_share.tput", "round_us.tput", "device_idle_share.tput", "fleet_useful_round_share",
    "fleet_commit_ms_p50", "state_build_s", "warmup_programs", "compiles_in_window",
    "setup_trace_s", "setup_lower_s", "setup_load_s", "setup_create_s",
}


def held(path: str) -> dict:
    with open(os.path.join(tiny.REPO, path), encoding="utf-8") as handle:
        return json.load(handle)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    where = tiny.checkout(str(tmp_path_factory.mktemp("bench_grid")))
    path = os.path.join(where, CONFIG_FILE)
    with open(path, encoding="utf-8") as handle:
        config = json.load(handle)
    config.update(TINY)
    with open(path, "w", encoding="utf-8") as handle:
        json.dump(config, handle)
    return where


# -- the data: the source's grid, only the repetitions cut ----------------------


def test_the_cell_and_its_configuration_are_the_sources():
    entry = next(c for c in BENCH["configs"] if c["name"] == "paper-grid-1k")
    config = held(entry["file"])
    assert entry["file"] == CONFIG_FILE and entry["reduced"] == ["repetitions"] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "Fig. 11" in config["source"] and "K, H, L sensitivity study" in config["source"]
    # no shape of the source is changed: K, the grid's values, 1,000 processes
    assert (config["members"], config["slots"], config["k"]) == (1000, 1000, 10)
    assert (config["h_values"], config["l_values"], config["f_values"]) == (
        [6, 7, 8, 9], [1, 2, 3, 4], [2, 4, 8, 16])
    assert (config["repetitions"], config["repetitions_published"]) == (4, 20)
    assert config["tenants"] == 64 * config["repetitions"] == 256
    assert (config["cohorts"], config["fd_threshold"], config["delivery_spread"],
            config["fallback_rounds"], config["telemetry"]) == (8, 3, 8, 8, 1)
    assert set(config["assumed"]) == {
        "tenants", "cohorts", "fd_threshold", "delivery_spread", "fallback_rounds", "telemetry"}
    assert config["deployment"] == "fleet"  # the accepted fleet readers ask for one
    assert any("classic round" in line and "coordinator rule" in line for line in config["guarantees"])
    assert any("subset of the tenant's crashed set" in line for line in config["guarantees"])
    # the engine's shapes are paper-fleet-1k's: [256, 10, 1000], 8 cohorts
    fleet = held("benchmarks/configs/paper-fleet-1k.json")
    assert all(config[key] == fleet[key] for key in ("tenants", "members", "slots", "k", "cohorts", "fd_threshold"))
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "paper-grid-1k" and len(cell["why"]) <= 200
    traffic = held(f"benchmarks/traffic/{cell['traffic']}.json")
    assert (traffic["kind"], traffic["resolve"], traffic["plan_cycle"], traffic["arrival_seed"]) == (
        "grid", "until_membership", 5, 7)
    # a cycle of the traffic gives every combination its published repetitions
    assert config["repetitions"] * traffic["plan_cycle"] == config["repetitions_published"]


def test_the_grid_lays_the_combinations_out_h_outermost():
    from benchmarks import targets_fleet_grid

    triples = targets_fleet_grid.grid(held(CONFIG_FILE))
    assert triples.shape == (256, 3) and triples[:, 2].sum() == 1920  # crash pairs a step
    assert triples[0].tolist() == triples[3].tolist() == [6, 1, 2] and triples[4].tolist() == [6, 1, 4]
    assert triples[-1].tolist() == [9, 4, 16] and len({tuple(t) for t in triples.tolist()}) == 64
    with pytest.raises(ValueError, match="are not the configuration's"):
        targets_fleet_grid.grid(dict(held(CONFIG_FILE), tenants=255))


def test_the_cell_is_on_the_lists_of_the_metrics_it_reports():
    listed = {
        group: {m["name"] for m in BENCH[group] if CELL in m.get("workloads", [CELL])}
        for group in ("end_to_end", "per_layer")
    }
    assert listed["end_to_end"] == END_TO_END
    assert listed["per_layer"] == PER_LAYER
    for name in OWN:
        metric = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert metric["moves"] == "view_changes_per_s" and metric["workloads"] == [CELL]
        assert metric["source"] == "program_counter"
        assert os.path.exists(os.path.join(tiny.REPO, "benchmarks", "metrics", name + ".py"))


# -- the plain reference, by itself -----------------------------------------------


def ring_of(members: int, k: int = 10) -> np.ndarray:
    """[k, members]: on ring r member s is observed by s + r + 1."""
    return (np.arange(members)[None, :] + np.arange(1, k + 1)[:, None]) % members


def test_the_reference_announces_when_a_subject_is_at_h_and_none_between():
    observers, victims = ring_of(40), np.asarray([3, 20])
    delays = np.zeros((2, 2, 10), dtype=np.int64)
    delays[1, 1, :5] = 4  # cohort 1 hears five of member 20's ten reports four rounds late
    fired = 2  # fd_threshold 3: the observers fire in the third round
    at, cuts = detector_model.announcements(
        observers=observers, victims=victims, delays=delays, high=9, low=3, fd_threshold=3)
    # five reports hold member 20 between the watermarks: the cohort waits for the rest
    assert at.tolist() == [fired, fired + 4] and cuts.all()
    at, cuts = detector_model.announcements(
        observers=observers, victims=victims, delays=delays, high=6, low=6, fd_threshold=3)
    # with L = H = 6 five reports hold nothing: cohort 1 announces member 3 alone
    assert at.tolist() == [fired, fired] and cuts.tolist() == [[True, True], [True, False]]


def test_the_reference_counts_a_crashed_observers_edge_once_it_is_at_l():
    observers, victims = ring_of(40), np.asarray([3, 4, 5])  # 4 and 5 observe 3, 5 observes 4
    delays = np.zeros((1, 3, 10), dtype=np.int64)
    at, cuts = detector_model.announcements(
        observers=observers, victims=victims, delays=delays, high=9, low=3, fd_threshold=3)
    # member 3 has eight healthy observers: under H = 9 until the two implicit reports count
    assert at.tolist() == [2] and cuts.all()
    at, cuts = detector_model.announcements(
        observers=observers, victims=victims, delays=delays, high=9, low=9, fd_threshold=3)
    # at L = 9 member 3's eight reports are not in flux: nothing is implied, nothing held back
    assert at.tolist() == [2] and cuts.tolist() == [[False, True, True]]


def test_the_reference_pools_votes_by_value_and_falls_back_by_the_clock():
    live = np.asarray([125] * 8)
    same = np.ones((8, 2), dtype=bool)
    at = np.asarray([2, 2, 2, 2, 2, 2, 5, 9])
    decided = detector_model.decision(members=1000, live=live, announced=at, cuts=same, fallback_rounds=8)
    # six cohorts are 750 votes, one under the quorum of 751: the seventh decides
    assert (decided["path"], decided["round"], decided["dissent"], decided["whole"]) == ("fast", 5, 0, True)
    split = same.copy()
    split[5:, 1] = False  # three cohorts announce a cut that misses a victim
    decided = detector_model.decision(members=1000, live=live, announced=at, cuts=split, fallback_rounds=8)
    # no value can reach 751: the classic round, eight rounds after the first
    # announcement, picks the value most voted; the cohort of round 9 is counted too
    assert (decided["path"], decided["round"], decided["dissent"]) == ("classic", 9, 3)
    assert decided["cut"].all() and decided["votes"] == 625
    split[4, 1] = False  # four against four: the rule allows either
    at[:] = 2
    decided = detector_model.decision(members=1000, live=live, announced=at, cuts=split, fallback_rounds=8)
    assert (decided["path"], decided["cut"], decided["dissent"], decided["whole"]) == ("classic", None, None, False)
    silent = detector_model.decision(
        members=1000, live=live, announced=np.full(8, -1), cuts=same, fallback_rounds=8)
    assert silent["path"] == "none"


def test_the_reference_imports_nothing_of_the_program():
    for module in ("detector_model", "consensus_model", "membership_model"):
        with open(os.path.join(tiny.REPO, "benchmarks", module + ".py"), encoding="utf-8") as handle:
            source = handle.read()
        assert "rapid_tpu" not in source.split('"""', 2)[2] and "import jax" not in source


# -- the cell, small, through the harness ---------------------------------------


def test_the_cell_runs_end_to_end_and_is_correct(checkout):
    done = tiny.run_cell(checkout, CELL, seed=2**31 + 77, seconds=1.0)
    result = tiny.result_of(done)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 5 and result["attempted"] % 5 == 0  # whole cycles
    assert set(result["metrics"]) == END_TO_END
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert "plan 4: the plain reference expects" in done.stdout and "on the classic path" in done.stdout
    assert "steps (plan: lockstep rounds / the fastest tenant's" in done.stdout
    for name in ("healthy_evicted", "crashed_in_view", "unresolved", "cut_sizes_unaccounted",
                 "view_changes_out_of_range", "compiles_in_window"):
        assert f"check {name}: value=0 limit=0" in done.stdout


def test_a_traced_run_reports_the_cells_per_layer_metrics(checkout):
    done = tiny.run_cell(checkout, CELL, seed=5, seconds=1.0, trace=1)
    result = tiny.result_of(done)
    assert result["correct"] is True
    assert set(result["metrics"]) == PER_LAYER
    metrics = {name: entry["value"] for name, entry in result["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    # some tenant takes the classic round in some step, and all 64 pay the arm with it
    assert 0 < metrics["tenant_classic_share"] < 10 and 0 < metrics["fleet_classic_round_share"] < 20
    assert metrics["dissent_per_step"] > 0
    # the tenants end in different rounds: the fast ones coast while the slowest resolves
    assert 50 < metrics["fleet_useful_round_share"] < 100
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result


def test_the_readers_read_a_recorded_run_and_nothing_where_nothing_is_kept():
    from benchmarks.metrics import dissent_per_step, fleet_classic_round_share, tenant_classic_share

    bare = {"counters_before": {"dispatch_ms": {}}, "counters_after": {"dispatch_ms": {}}, "attempted": 4}
    assert fleet_classic_round_share.read(bare) is None
    assert tenant_classic_share.read(bare) is None and dissent_per_step.read(bare) is None
    recorded = dict(
        bare, tenant_steps=1024, tenant_steps_classic=32, dissent=150,
        counters_before={"dispatch_ms": {}, "fleet": {
            "engine_fleet_wave_rounds": 30, "engine_fleet_classic_rounds": 4}},
        counters_after={"dispatch_ms": {}, "fleet": {
            "engine_fleet_wave_rounds": 90, "engine_fleet_classic_rounds": 13}})
    assert fleet_classic_round_share.read(recorded) == 15.0
    assert tenant_classic_share.read(recorded) == 3.125 and dissent_per_step.read(recorded) == 37.5


# -- the controls ---------------------------------------------------------------


@pytest.mark.parametrize("fault,broken", [
    ("never_fall_back", "unresolved"),
    ("one_triple_for_all", "view_changes_out_of_range"),
    ("evict_healthy", "healthy_evicted"),
    ("lose_crash", "crashed_in_view"),
])
def test_the_controls_come_out_not_correct(checkout, fault, broken):
    done = tiny.run_cell(checkout, CELL, seconds=0.2, script="benchmarks/control_grid.py",
                         extra=("--fault", fault))
    result = tiny.result_of(done)
    assert result["correct"] is False and result["failed"] == result["attempted"] > 0
    over = [line for line in done.stdout.splitlines() if line.startswith(f"check {broken}: value=")]
    assert over and int(over[0].split("value=")[1].split()[0]) > 0
    if fault == "one_triple_for_all":  # everybody agreed and decided fast: only the path is off
        for name in ("unresolved", "healthy_evicted", "crashed_in_view", "cut_sizes_unaccounted"):
            assert f"check {name}: value=0 limit=0" in done.stdout
