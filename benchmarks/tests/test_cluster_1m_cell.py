"""``cluster-1m.crash1`` rehearsed on the CPU at a few thousand members, and
the data checks that hold the cell to its source's sizes. The cell keeps its
name, its traffic file, its generator and its readers; this file swaps the
configuration's size for a tiny one in a throw-away checkout of its own
(``tiny.py`` knows the cells of the first benchmark only). Run with
``python -m pytest benchmarks/tests/test_cluster_1m_cell.py -q`` from the root.
"""

from __future__ import annotations

import json
import os

import pytest

from benchmarks import bytes as kernel_bytes
from benchmarks.tests import tiny

CELL = "cluster-1m.crash1"
CONFIG_FILE = "benchmarks/configs/cluster-1m.json"
BENCH = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8"))
#: As many slots as members, ragged against the kernel's 128-lane tile, as at 1M.
TINY = {"members": 4100, "slots": 4100}
#: The thirteen metrics ISSUE 31 lists for the cell.
END_TO_END = {"commit_ms_p50", "setup_s"}
PER_LAYER = {
    "host_blocked_share.commit", "d2h_bytes_per_commit.commit", "round_us.commit",
    "rounds_per_commit.commit", "device_idle_share.commit", "inject_crash_ms.commit",
    "sync_ms.commit", "delivery_kernel_us", "delivery_roofline", "state_build_s",
    "warmup_programs", "compiles_in_window",
}


def held(path: str) -> dict:
    with open(os.path.join(tiny.REPO, path), encoding="utf-8") as handle:
        return json.load(handle)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    where = tiny.checkout(str(tmp_path_factory.mktemp("bench_1m")))
    path = os.path.join(where, CONFIG_FILE)
    with open(path, encoding="utf-8") as handle:
        config = json.load(handle)
    config.update(TINY)
    with open(path, "w", encoding="utf-8") as handle:
        json.dump(config, handle)
    return where


def run_cell(where: str, **kw):
    return tiny.run_cell(where, CELL, seconds=0.5, **kw)


# -- the data: the cell is the source's deployment, nothing cut ----------------


def test_the_cell_and_its_configuration_are_the_sources():
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "crash1" and cell["config"] == "cluster-1m"
    entry = next(c for c in BENCH["configs"] if c["name"] == "cluster-1m")
    assert entry["file"] == CONFIG_FILE and entry["reduced"] == []
    config = held(CONFIG_FILE)
    assert config["deployment"] == "cluster" and config["source"] == entry["source"]
    assert config["members"] == config["slots"] == 1_000_000
    assert (config["k"], config["h"], config["l"]) == (10, 9, 4)
    assert config["cohorts"] == 8 and config["cohort_assignment"] == "roundrobin"
    assert config["fd_threshold"] == 3 and "fd_stagger_rounds" not in config
    assert config["delivery_spread"] == 2 and config["concurrent_coordinators"] == 1
    assert config["use_pallas"] is True and config["pallas_lanes"] == 128
    assert config["reduced"] == [] and config["assumed"] == [
        "slots", "cohorts", "delivery_spread", "fd_threshold", "concurrent_coordinators", "pallas_lanes"]
    assert config["guarantees"] == held("benchmarks/configs/cluster-100k.json")["guarantees"]
    assert config["guarantees"] == held("benchmarks/configs/cluster-10m.json")["guarantees"]


def test_the_cell_takes_the_batched_view_change():
    from rapid_tpu.ops import rings

    slots = held(CONFIG_FILE)["slots"]
    # Below the threshold the K rings are rebuilt in one vmap; a change that
    # moves the constant over this cell changes what the cell measures.
    assert slots < 1 << 22 and slots < rings.RING_AT_A_TIME_SLOTS


def test_the_traffic_is_the_four_chip_cells_file_unedited():
    traffic = held("benchmarks/traffic/crash1.json")
    assert traffic["crash_share"] == 0.01 and traffic["join_share"] == 0
    assert traffic["resolve"] == "to_decision" and "plan_cycle" not in traffic
    assert traffic["kind"] == "closed_loop_mesh"  # registers one more deployment, then closed_loop.run
    ten_m = next(c for c in BENCH["workloads"] if c["name"] == "cluster-10m.crash1")
    assert ten_m["traffic"] == "crash1"


def test_the_cell_is_on_the_lists_of_the_metrics_it_reports():
    end_to_end = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    per_layer = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert end_to_end == END_TO_END and per_layer == PER_LAYER
    for metric in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in metric.get("workloads", []):
            assert metric["workloads"][-1] == CELL  # appended, nothing moved


def test_the_kernels_least_bytes_at_the_cells_shape():
    config = held(CONFIG_FILE)
    need = kernel_bytes.delivery_new_bits(
        config["slots"], config["cohorts"], config["k"], config["pallas_lanes"])
    # one cohort word; 7,812.5 tiles of 128 lanes round up to 1,000,064 lanes
    assert need["bytes"] == 4 * 1_000_064 * (10 + 10 + 32) == 208_013_312
    peak = held("benchmarks/peaks.json")["TPU v5 lite"]
    least, bound = kernel_bytes.least_seconds(need, peak)
    assert bound == "memory" and least * 1e6 == pytest.approx(254.0, abs=0.5)


# -- the cell, end to end, tiny, on the CPU ------------------------------------


def test_untraced_run_prints_the_contracts_line(checkout):
    done = run_cell(checkout, seed=4294967301)
    result = tiny.result_of(done)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == END_TO_END
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["count"] == 1
    assert "check compiles_in_window: value=0 limit=0" in done.stdout
    # every step is one decision: as many rounds entries as steps, none at the limit
    line = next(l for l in done.stdout.splitlines() if l.startswith("commits"))
    rounds = [int(token.split(":")[1]) for token in line.split(": ", 1)[1].split()]
    assert len(rounds) == result["attempted"] and all(1 <= r < 64 for r in rounds)


def test_traced_run_reports_the_cells_per_layer_metrics(checkout):
    done = run_cell(checkout, seed=11, trace=1)
    result = tiny.result_of(done)
    # no Mosaic kernel runs in a CPU rehearsal, so its readers find nothing
    assert set(result["metrics"]) == PER_LAYER - {"delivery_kernel_us", "delivery_roofline"}
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["metrics"]["rounds_per_commit.commit"]["value"] >= 1
    assert result["metrics"]["d2h_bytes_per_commit.commit"]["value"] == 8  # sync's 4, the decision's 4
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]


@pytest.mark.parametrize("fault,number", [
    ("evict_healthy", "healthy_evicted"), ("lose_crash", "crashed_in_view"),
])
def test_broken_path_comes_out_not_correct(checkout, fault, number):
    done = run_cell(checkout, seed=99, script="benchmarks/control.py", extra=("--fault", fault))
    result = tiny.result_of(done)
    assert result["correct"] is False and result["failed"] > 0
    line = next(l for l in done.stdout.splitlines() if l.startswith(f"check {number}:"))
    assert int(line.split("value=")[1].split()[0]) > 0
