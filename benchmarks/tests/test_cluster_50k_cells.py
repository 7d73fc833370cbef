"""``cluster-50k.loss80`` and ``cluster-50k.flipflop`` rehearsed on the CPU at a
few thousand members, and the data checks that hold the cells to their source's
sizes. The cells keep their names, traffic files, generator, target, readers
and controls; this file swaps the configuration's size for a small one in a
throw-away checkout of its own (``tiny.py`` knows the cells of the first
benchmark only). Run with
``python -m pytest benchmarks/tests/test_cluster_50k_cells.py -q`` from the root.
"""

from __future__ import annotations

import json
import os

import pytest

from benchmarks.tests import tiny

CELLS = ("cluster-50k.loss80", "cluster-50k.flipflop")
CONFIG_FILE = "benchmarks/configs/cluster-50k.json"
BENCH = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8"))
#: 2,000 members, 20 of them faulty a step, 4 cohorts.
TINY = {"members": 2000, "slots": 2000, "cohorts": 4}
END_TO_END = {"commit_ms_p50", "setup_s"}
PER_LAYER = {
    "host_blocked_share.commit", "d2h_bytes_per_commit.commit", "round_us.commit",
    "rounds_per_commit.commit", "delivery_kernel_us", "delivery_roofline",
    "device_idle_share.commit", "sync_ms.commit", "state_build_s", "warmup_programs",
    "compiles_in_window", "setup_trace_s", "setup_lower_s", "setup_load_s", "setup_create_s",
    "inject_link_ms.commit", "link_probes_lost_per_round",
}
#: What a CPU run has nothing to read for: the Mosaic kernel runs on the chip only.
CHIP_ONLY = {"delivery_kernel_us", "delivery_roofline"}


def held(path: str) -> dict:
    with open(os.path.join(tiny.REPO, path), encoding="utf-8") as handle:
        return json.load(handle)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    where = tiny.checkout(str(tmp_path_factory.mktemp("bench_50k")))
    path = os.path.join(where, CONFIG_FILE)
    with open(path, encoding="utf-8") as handle:
        config = json.load(handle)
    config.update(TINY)
    with open(path, "w", encoding="utf-8") as handle:
        json.dump(config, handle)
    return where


# -- the data: the cells are the source's deployment, nothing cut --------------


def test_the_cells_and_their_configuration_are_the_sources():
    entry = next(c for c in BENCH["configs"] if c["name"] == "cluster-50k")
    config = held(entry["file"])
    assert entry["file"] == CONFIG_FILE and entry["reduced"] == [] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert (config["members"], config["slots"], config["cohorts"]) == (50_000, 50_000, 64)
    assert (config["k"], config["h"], config["l"]) == (10, 9, 4)
    assert (config["fd_window"], config["fd_threshold"]) == (10, 4)
    assert config["deployment"] == "cluster_link" and config["use_pallas"] is True
    assert set(config["assumed"]) == {
        "slots", "cohorts", "delivery_spread", "concurrent_coordinators", "pallas_lanes", "l"}
    assert any("exactly the faulty set" in line for line in config["guarantees"])
    for name in CELLS:
        cell = next(c for c in BENCH["workloads"] if c["name"] == name)
        assert cell["chips"] == 1 and cell["config"] == "cluster-50k" and len(cell["why"]) <= 200
        traffic = held(f"benchmarks/traffic/{cell['traffic']}.json")
        assert traffic["kind"] == "link_faults" and traffic["faulty_share"] == 0.01
        assert (traffic["plan_cycle"], traffic["arrival_seed"]) == (16, 7)
    assert held("benchmarks/traffic/loss80.json")["ingress_loss_permille"] == 800
    flip = held("benchmarks/traffic/flipflop.json")
    assert (flip["ingress_loss_permille"], flip["on_rounds"], flip["off_rounds"]) == (1000, 20, 20)


@pytest.mark.parametrize("cell", CELLS)
def test_the_cell_is_on_the_lists_of_the_metrics_it_reports(cell):
    listed = {
        group: {m["name"] for m in BENCH[group] if cell in m.get("workloads", [cell])}
        for group in ("end_to_end", "per_layer")
    }
    assert listed["end_to_end"] == END_TO_END
    assert listed["per_layer"] == PER_LAYER
    for name in ("inject_link_ms.commit", "link_probes_lost_per_round"):
        metric = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert metric["moves"] == "commit_ms_p50" and set(metric["workloads"]) == set(CELLS)


# -- the cells, small, through the harness --------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_the_cell_runs_end_to_end_and_is_correct(checkout, cell):
    done = tiny.run_cell(checkout, cell, seed=2**31 + 77, seconds=1.0)
    result = tiny.result_of(done)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 16 and result["attempted"] % 16 == 0  # whole cycles
    assert set(result["metrics"]) == END_TO_END
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert "redrawn for the precondition" in done.stdout
    for name in ("healthy_evicted", "crashed_in_view", "unresolved", "cut_sizes_unaccounted"):
        assert f"check {name}: value=0 limit=0" in done.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reports_the_cells_per_layer_metrics(checkout, cell):
    done = tiny.run_cell(checkout, cell, seed=5, seconds=1.0, trace=1)
    result = tiny.result_of(done)
    assert result["correct"] is True
    assert set(result["metrics"]) == PER_LAYER - CHIP_ONLY
    metrics = {name: entry["value"] for name, entry in result["metrics"].items()}
    # 4 the sync's checksum, 28 the wave's observation, 4 the lane's count
    assert metrics["d2h_bytes_per_commit.commit"] == 36
    assert metrics["compiles_in_window"] == 0 and metrics["inject_link_ms.commit"] > 0
    # 20 faulty members: 200 edges in and about 200 out, less those between two
    # of them; every one fails on flipflop, four of five on loss80
    lost = metrics["link_probes_lost_per_round"]
    assert (370 <= lost <= 400) if cell.endswith("flipflop") else (280 <= lost <= 330)
    assert metrics["rounds_per_commit.commit"] < 20
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result


# -- the controls ---------------------------------------------------------------


@pytest.mark.parametrize("fault,broken", [
    ("lose_fault", "crashed_in_view"), ("deafen_healthy", "healthy_evicted"),
])
def test_the_controls_come_out_not_correct(checkout, fault, broken):
    done = tiny.run_cell(
        checkout, CELLS[1], seconds=0.5, script="benchmarks/control_link.py", extra=("--fault", fault))
    result = tiny.result_of(done)
    assert result["correct"] is False and result["failed"] == result["attempted"] > 0
    assert f"check {broken}: value=1 limit=0" in done.stdout
    assert "check unresolved: value=1 limit=0" in done.stdout


def test_the_crash_controls_break_nothing_here(checkout):
    """``control.py``'s faults patch crash injection, which this traffic never
    calls: they leave the run correct, which is why the cells have their own."""
    done = tiny.run_cell(
        checkout, CELLS[1], seconds=0.5, script="benchmarks/control.py", extra=("--fault", "lose_crash"))
    assert tiny.result_of(done)["correct"] is True
