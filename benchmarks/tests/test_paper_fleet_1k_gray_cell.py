"""``paper-fleet-1k-gray.ingress80`` rehearsed on the CPU at 12 tenants of 400
members, and the data checks that hold the cell to its source's shapes. The
cell keeps its name, traffic file, generator, target, reference, readers and
controls; this file swaps the configuration's size for a small one in a
throw-away checkout of its own (``tiny.py`` knows the cells of the first
benchmark only). Run with
``python -m pytest benchmarks/tests/test_paper_fleet_1k_gray_cell.py -q`` from the root.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmarks import link_model
from benchmarks.tests import tiny

CELL = "paper-fleet-1k-gray.ingress80"
CONFIG_FILE = "benchmarks/configs/paper-fleet-1k-gray.json"
BENCH = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8"))
#: 12 tenants of 400 members (4 faulty each), 4 cohorts.
TINY = {"tenants": 12, "members": 400, "slots": 400, "cohorts": 4}
END_TO_END = {"view_changes_per_s", "setup_s"}
OWN = {"inject_link_ms.tput", "link_probes_lost_per_round.tput"}
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
    where = tiny.checkout(str(tmp_path_factory.mktemp("bench_gray")))
    path = os.path.join(where, CONFIG_FILE)
    with open(path, encoding="utf-8") as handle:
        config = json.load(handle)
    config.update(TINY)
    with open(path, "w", encoding="utf-8") as handle:
        json.dump(config, handle)
    return where


# -- the data: the source's shapes, only the tenants assumed ----------------------


def test_the_cell_and_its_configuration_are_the_sources():
    entry = next(c for c in BENCH["configs"] if c["name"] == "paper-fleet-1k-gray")
    config = held(entry["file"])
    assert entry["file"] == CONFIG_FILE and entry["reduced"] == ["tenants"] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "Fig. 10" in config["source"] and "Asymmetric network failures" in config["source"]
    # no shape of the source is changed: N, K, H, L, the detector
    assert (config["members"], config["slots"], config["k"], config["h"], config["l"]) == (1000, 1000, 10, 9, 3)
    assert (config["fd_window"], config["fd_threshold"], config["fd_stagger_rounds"]) == (10, 4, 0)
    assert (config["tenants"], config["cohorts"], config["delivery_spread"]) == (256, 8, 2)
    assert set(config["assumed"]) == {"tenants", "cohorts", "delivery_spread"}
    assert config["deployment"] == "fleet"  # the accepted fleet readers ask for one
    assert any("exactly the faulty set is removed" in line for line in config["guarantees"])
    assert any("no healthy member is evicted" in line for line in config["guarantees"])
    # the engine's shapes are paper-fleet-1k's, the control's: [256, 10, 1000], 8 cohorts, {10, 9, 3}
    control = held("benchmarks/configs/paper-fleet-1k.json")
    assert all(config[key] == control[key] for key in (
        "tenants", "members", "slots", "k", "h", "l", "cohorts", "delivery_spread"))
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "paper-fleet-1k-gray" and len(cell["why"]) <= 200
    traffic = held(f"benchmarks/traffic/{cell['traffic']}.json")
    assert (traffic["kind"], traffic["resolve"], traffic["plan_cycle"], traffic["arrival_seed"]) == (
        "fleet_link_faults", "until_membership", 8, 7)
    # the paper's fault: 1 % of the processes at 80 % ingress loss, steadily
    assert (traffic["faulty_share"], traffic["ingress_loss_permille"]) == (0.01, 800)
    assert (traffic["on_rounds"], traffic["off_rounds"]) == (0, 0)


def test_the_cell_is_on_the_lists_of_the_metrics_it_reports():
    listed = {
        group: {m["name"] for m in BENCH[group] if CELL in m.get("workloads", [CELL])}
        for group in ("end_to_end", "per_layer")
    }
    assert listed["end_to_end"] == END_TO_END
    assert listed["per_layer"] == PER_LAYER
    for name, source in (("inject_link_ms.tput", "program_span"),
                         ("link_probes_lost_per_round.tput", "program_counter")):
        metric = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert metric["moves"] == "view_changes_per_s" and metric["workloads"] == [CELL]
        assert (metric["source"], metric["better"]) == (source, "lower")
        # the accepted readers: the harness takes the stem before the dot
        assert os.path.exists(os.path.join(tiny.REPO, "benchmarks", "metrics", name.split(".")[0] + ".py"))
        layers = {m["layer"] for m in BENCH["per_layer"] if m["name"].split(".")[0] == name.split(".")[0]}
        assert len(layers) == 1  # the layer's name, letter for letter, as the accepted entry has it


def test_the_reference_imports_nothing_of_the_program():
    for module in ("link_model", "membership_model"):
        with open(os.path.join(tiny.REPO, "benchmarks", module + ".py"), encoding="utf-8") as handle:
            source = handle.read()
        assert "rapid_tpu" not in source.split('"""', 2)[2] and "import jax" not in source


def test_the_precondition_redraws_a_set_that_would_hold_a_proposal_back():
    # ring r: member s is observed by s + r + 1; with 5, 6, 7 faulty member 4
    # has three of its ten observers in the set: at L = 3 it sits in [L, H)
    observers = (np.arange(40)[None, :] + np.arange(1, 11)[:, None]) % 40
    reports = link_model.false_reports(observers, [5, 6, 7])
    assert reports[4] == 3 and reports[8] == 0
    reports[[5, 6, 7]] = 0
    assert (reports >= 3).any() and not (link_model.false_reports(observers, [5, 20])[[4, 19]] >= 3).any()


# -- the cell, small, through the harness ---------------------------------------


def test_the_cell_runs_end_to_end_and_is_correct(checkout):
    done = tiny.run_cell(checkout, CELL, seed=2**31 + 77, seconds=1.0)
    result = tiny.result_of(done)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 8 and result["attempted"] % 8 == 0  # whole cycles
    assert set(result["metrics"]) == END_TO_END
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert "faulty sets: 8 plans of 12 tenants x 4 members" in done.stdout
    assert "tenant-draws redrawn for the precondition" in done.stdout
    assert "steps (plan: lockstep rounds / the fastest tenant's / cuts)" in done.stdout
    for name in ("healthy_evicted", "crashed_in_view", "strangers_in_view", "unresolved",
                 "cut_sizes_unaccounted", "config_id_not_advanced", "view_changes_out_of_range",
                 "compiles_in_window"):
        assert f"check {name}: value=0 limit=0" in done.stdout


def test_a_traced_run_reports_the_cells_per_layer_metrics(checkout):
    done = tiny.run_cell(checkout, CELL, seed=5, seconds=1.0, trace=1)
    result = tiny.result_of(done)
    assert result["correct"] is True
    assert set(result["metrics"]) == PER_LAYER
    metrics = {name: entry["value"] for name, entry in result["metrics"].items()}
    assert metrics["compiles_in_window"] == 0 and metrics["inject_link_ms.tput"] > 0
    # about 2 K m p t a round while the faulty sets are in the view, less for
    # the tenants that have committed while the slowest still runs
    full = 2 * 10 * 4 * 0.8 * TINY["tenants"]
    assert 0.3 * full < metrics["link_probes_lost_per_round.tput"] < full
    # the tenants end in different rounds: the fast ones coast while the slowest resolves
    assert 40 < metrics["fleet_useful_round_share"] < 100
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result


def test_the_readers_read_a_recorded_run_and_nothing_where_nothing_is_kept():
    from benchmarks.metrics import inject_link_ms, link_probes_lost_per_round

    bare = {"counters_before": {"dispatch_ms": {}}, "counters_after": {"dispatch_ms": {"fleet_wave": 9.0}},
            "attempted": 4, "rounds": 48}
    assert inject_link_ms.read(bare) is None and link_probes_lost_per_round.read(bare) is None
    recorded = dict(
        bare, commit_ms=[1.0] * 4,
        counters_before={"dispatch_ms": {"inject_link_faults": 2.0}, "link": {"probes_lost": 1000}},
        counters_after={"dispatch_ms": {"inject_link_faults": 6.0}, "link": {"probes_lost": 1_921_000}})
    assert inject_link_ms.read(recorded) == 1.0
    assert link_probes_lost_per_round.read(recorded) == 40_000.0


def test_a_program_without_the_setter_fails_before_it_builds_anything(checkout, tmp_path):
    # the parent's program: ``TenantFleet`` with no ``set_link_faults``
    shim = tmp_path / "sitecustomize.py"
    shim.write_text(
        "from rapid_tpu.tenancy import fleet\n"
        "del fleet.TenantFleet.set_link_faults\n"
        "fleet.TenantFleet.create = classmethod(lambda *a, **k: (_ for _ in ()).throw(SystemExit('built')))\n")
    done = tiny.run_cell(checkout, CELL, seconds=0.2, pythonpath=os.pathsep.join([str(tmp_path), tiny.REPO]))
    assert done.returncode == 1 and "has no set_link_faults" in done.stderr
    assert "built" not in done.stderr and "correct" not in done.stdout


# -- the controls ---------------------------------------------------------------


@pytest.mark.parametrize("fault,broken", [
    ("lose_fault", "crashed_in_view"), ("deafen_healthy", "healthy_evicted")])
def test_the_controls_come_out_not_correct(checkout, fault, broken):
    done = tiny.run_cell(checkout, CELL, seconds=0.2, script="benchmarks/control_fleet_link.py",
                         extra=("--fault", fault))
    result = tiny.result_of(done)
    assert result["correct"] is False and result["failed"] == result["attempted"] > 0
    over = {
        line.split()[1].rstrip(":"): int(line.split("value=")[1].split()[0])
        for line in done.stdout.splitlines() if line.startswith("check ")
    }
    # in every tenant: a faulty member stays, or a healthy one goes; none resolves
    assert over[broken] >= TINY["tenants"] and over["unresolved"] == TINY["tenants"]
    assert over["strangers_in_view"] == 0 and over["compiles_in_window"] == 0
