"""``paper-fleet-2k.bootstrap`` rehearsed on the CPU at a few tenants of a few
dozen slots, and the data checks that hold the cell to its source's sizes. The
cell keeps its name, its traffic file, its generator, its target and its
readers; this file swaps the configuration's size for a tiny one in a
throw-away checkout of its own (``tiny.py`` knows the cells of the first
benchmark only). Run with
``python -m pytest benchmarks/tests/test_paper_fleet_2k_cell.py -q`` from the root.
"""

from __future__ import annotations

import json
import os

import pytest

from benchmarks.tests import tiny

CELL = "paper-fleet-2k.bootstrap"
CONFIG_FILE = "benchmarks/configs/paper-fleet-2k.json"
BENCH = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8"))
#: 3 tenants, 16 -> 80 in 8 waves of 8 joiners a tenant.
TINY = {"tenants": 3, "members": 16, "slots": 80, "cohorts": 4}
END_TO_END = {"commit_ms_p50", "setup_s"}
PER_LAYER = {
    "host_blocked_share.commit", "d2h_bytes_per_commit.commit", "round_us.commit",
    "rounds_per_commit.commit", "device_idle_share.commit", "inject_join_admit_ms.commit",
    "inject_join_place_ms.commit", "state_build_s", "warmup_programs", "compiles_in_window",
    "cuts_per_bootstrap", "wave_gate_round_share.commit",
}


def held(path: str) -> dict:
    with open(os.path.join(tiny.REPO, path), encoding="utf-8") as handle:
        return json.load(handle)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    where = tiny.checkout(str(tmp_path_factory.mktemp("bench_2k")))
    path = os.path.join(where, CONFIG_FILE)
    with open(path, encoding="utf-8") as handle:
        config = json.load(handle)
    config.update(TINY)
    with open(path, "w", encoding="utf-8") as handle:
        json.dump(config, handle)
    return where


def run_cell(where: str, **kw):
    return tiny.run_cell(where, CELL, seconds=1.0, **kw)


# -- the data: the cell is the source's deployment, only the tenants cut -------


def test_the_cell_and_its_configuration_are_the_sources():
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "bootstrap" and cell["config"] == "paper-fleet-2k"
    entry = next(c for c in BENCH["configs"] if c["name"] == "paper-fleet-2k")
    assert entry["file"] == CONFIG_FILE and entry["reduced"] == ["tenants"]
    config = held(CONFIG_FILE)
    assert config["deployment"] == "fleet" and config["source"] == entry["source"]
    assert len(config["source"]) <= 200 and "Fig. 5" in config["source"] and "Table 1" in config["source"]
    assert (config["tenants"], config["members"], config["slots"]) == (128, 64, 2000)
    assert (config["k"], config["h"], config["l"]) == (10, 9, 3)
    assert config["cohorts"] == 8 and config["cohort_assignment"] == "roundrobin"
    assert config["fd_threshold"] == 3 and config["delivery_spread"] == 2
    assert config["reduced"] == ["tenants"]
    assert config["assumed"] == ["tenants", "members", "cohorts", "fd_threshold", "delivery_spread"]
    # the published shapes are paper-fleet-1k's: same source, same {K,H,L}
    one_k = held("benchmarks/configs/paper-fleet-1k.json")
    assert all(config[key] == one_k[key] for key in ("k", "h", "l", "cohorts", "fd_threshold", "delivery_spread"))
    assert config["tenants"] * config["slots"] == one_k["tenants"] * one_k["slots"] == 256_000


def test_the_traffic_is_eight_equal_waves():
    traffic, config = held("benchmarks/traffic/bootstrap.json"), held(CONFIG_FILE)
    assert traffic["kind"] == "bootstrap" and traffic["waves"] == 8
    assert traffic["resolve"] == "until_membership"
    assert (config["slots"] - config["members"]) == 8 * 242


def test_the_cell_is_on_the_lists_of_the_metrics_it_reports():
    end_to_end = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    per_layer = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert end_to_end == END_TO_END and per_layer == PER_LAYER
    # (not "and it is the last of each list": the next cell's append would falsify that)
    for name in ("cuts_per_bootstrap", "wave_gate_round_share.commit"):
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "commit_ms_p50"


def test_the_generator_imports_nothing_of_the_program():
    for name in ("generators/bootstrap.py", "control_join.py", "metrics/cuts_per_bootstrap.py",
                 "metrics/wave_gate_round_share.py", "targets_fleet_join.py"):
        with open(os.path.join(tiny.REPO, "benchmarks", name), encoding="utf-8") as handle:
            assert "rapid_tpu" not in handle.read().replace("rapid_tpu/", ""), name


# -- the cell, end to end, tiny, on the CPU ------------------------------------


def test_untraced_run_prints_the_contracts_line(checkout):
    done = run_cell(checkout, seed=4294967301)
    result = tiny.result_of(done)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == END_TO_END
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert "check compiles_in_window: value=0 limit=0" in done.stdout
    # every step is a whole bootstrap: eight waves' lockstep rounds, none at its limit
    line = next(l for l in done.stdout.splitlines() if l.startswith("commits"))
    rounds = [int(token.split(":")[1]) for token in line.split(": ", 1)[1].split()]
    assert len(rounds) == result["attempted"] and all(8 <= r < 8 * 192 for r in rounds)


def test_traced_run_reports_the_cells_per_layer_metrics(checkout):
    done = run_cell(checkout, seed=11, trace=1)
    result = tiny.result_of(done)
    metrics = result["metrics"]
    assert set(metrics) == PER_LAYER
    assert metrics["compiles_in_window"]["value"] == 0
    assert 8 <= metrics["cuts_per_bootstrap"]["value"] <= 16
    assert 0 < metrics["wave_gate_round_share.commit"]["value"] < 50
    assert 8 <= metrics["rounds_per_commit.commit"]["value"] < 64
    # a wave: one bool a joiner, then steps, cuts, resolved, four sizes a tenant and the loop's four counts
    assert metrics["d2h_bytes_per_commit.commit"]["value"] == 8 * (3 * 8 + 4 * (3 * 7 + 4))
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]


@pytest.mark.parametrize("fault,numbers", [
    ("lose_join", ("healthy_evicted", "unresolved")),
    ("admit_stranger", ("unresolved", "cut_sizes_unaccounted")),
])
def test_broken_join_path_comes_out_not_correct(checkout, fault, numbers):
    done = run_cell(checkout, seed=99, script="benchmarks/control_join.py", extra=("--fault", fault))
    result = tiny.result_of(done)
    assert result["correct"] is False and result["failed"] > 0
    for number in numbers:
        line = next(l for l in done.stdout.splitlines() if l.startswith(f"check {number}:"))
        assert int(line.split("value=")[1].split()[0]) > 0


def test_the_crash_controls_break_nothing_on_a_join_cell(checkout):
    # why the cell has controls of its own: control.py's faults patch crash
    # injection, which this traffic never calls
    done = run_cell(checkout, seed=99, script="benchmarks/control.py", extra=("--fault", "lose_crash"))
    assert tiny.result_of(done)["correct"] is True


def test_a_program_without_the_join_seam_fails_at_once(checkout, tmp_path):
    # the parent of PR 33: TenantFleet has no inject_join_wave
    shim = tmp_path / "sitecustomize.py"
    shim.write_text(
        "import rapid_tpu.tenancy.fleet as f\n"
        "del f.TenantFleet.inject_join_wave\n", encoding="utf-8")
    done = run_cell(checkout, seed=5, pythonpath=os.pathsep.join([str(tmp_path), tiny.REPO]))
    assert done.returncode != 0 and "inject_join_wave" in done.stderr
