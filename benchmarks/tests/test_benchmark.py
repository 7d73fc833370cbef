"""The benchmark's own tests: the contract of ``BENCHMARK.json``, every cell
end to end at tiny sizes on the CPU, the trace reduction on a recorded chip
trace, the numpy membership model, the controls, and that new cells and
metrics are new files and entries only. Run with
``python -m pytest benchmarks/tests -q`` from the repository's root.
"""

from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

from benchmarks import membership_model, trace_reduce
from benchmarks.tests import tiny

BENCH = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8"))
CELLS = [cell["name"] for cell in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.checkout(str(tmp_path_factory.mktemp("bench")))


# -- BENCHMARK.json against the contract ------------------------------------


def test_names_units_and_keys_are_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    for entry in BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for cell in BENCH["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["traffic"]) and cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    for config in BENCH["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        held = json.load(open(os.path.join(tiny.REPO, config["file"]), encoding="utf-8"))
        assert held["reduced"] == config["reduced"] and len(config["source"]) <= 200
        assert held["source"] == config["source"] and held["guarantees"]
    for metric in BENCH["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert metric["source"] in ("host_clock", "device_trace") and 0.01 <= metric["bound"] <= 0.25
    for metric in BENCH["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for metric in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert 1 <= BENCH["run_seconds"] <= 51 and BENCH["run_seconds"] == int(BENCH["run_seconds"])


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    reported = {
        cell: {m["name"] for m in BENCH["end_to_end"] if cell in m.get("workloads", CELLS)}
        for cell in CELLS
    }
    for cell in CELLS:
        assert "setup_s" in reported[cell] and len(reported[cell]) >= 2
    for metric in BENCH["per_layer"]:
        for cell in metric.get("workloads", CELLS):
            assert metric["moves"] in reported[cell], (metric["name"], cell)
        stem = metric["name"].split(".")[0]
        assert os.path.exists(os.path.join(tiny.REPO, "benchmarks", "metrics", stem + ".py"))


# -- every cell, end to end, tiny, on the CPU --------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_and_prints_the_contracts_line(checkout, cell):
    done = tiny.run_cell(checkout, cell, seed=4294967301, seconds=0.3)
    result = tiny.result_of(done)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    wanted = {m["name"] for m in BENCH["end_to_end"] if cell in m.get("workloads", CELLS)}
    assert set(result["metrics"]) == wanted
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert "check compiles_in_window: value=0 limit=0" in done.stdout


@pytest.mark.parametrize("cell", ["cluster-100k.churn5", "paper-fleet-1k.trickle"])
def test_traced_run_reports_the_cells_per_layer_metrics(checkout, cell):
    result = tiny.result_of(tiny.run_cell(checkout, cell, seed=11, seconds=0.3, trace=1))
    assert set(result) == RESULT_KEYS | {"breakdown"}
    wanted = {m["name"] for m in BENCH["per_layer"] if cell in m.get("workloads", CELLS)}
    # no Mosaic kernel runs in a CPU rehearsal, so its readers find nothing
    assert set(result["metrics"]) == wanted - {"delivery_kernel_us", "delivery_roofline"}
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert 1 <= len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10


def test_same_seed_same_inputs(checkout):
    steps = []
    for _ in range(2):
        done = tiny.run_cell(checkout, "paper-fleet-1k.trickle", seed=2**31 + 5, seconds=0.1)
        tiny.result_of(done)
        steps.append([line for line in done.stdout.splitlines() if line.startswith("check")])
    assert steps[0] == steps[1]


def test_a_fixed_draw_gives_every_seed_the_same_steps_in_another_order(checkout):
    orders = []
    for seed in (1, 2):
        done = tiny.run_cell(checkout, "cluster-100k.churn5", seed=seed, seconds=0.3)
        tiny.result_of(done)
        line = next(l for l in done.stdout.splitlines() if l.startswith("commits"))
        orders.append([int(token.split(":")[0]) for token in line.split(": ", 1)[1].split()])
    for order in orders:  # whole cycles, each holding every plan once
        assert len(order) % 16 == 0 and sorted(order[:16]) == list(range(16))
    assert orders[0][:16] != orders[1][:16]


def test_no_chip_and_no_rehearsal_flag_means_no_result(checkout):
    done = tiny.run_cell(checkout, "cluster-100k.churn5", platform=None)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout


def test_only_the_benchmarks_files_means_no_result(tmp_path):
    where = tiny.checkout(str(tmp_path))
    done = tiny.run_cell(where, "cluster-100k.churn5", pythonpath="")
    assert done.returncode != 0 and '"correct"' not in done.stdout


# -- the controls: a guarantee broken under the driver ------------------------


@pytest.mark.parametrize("cell,fault,number", [
    ("cluster-100k.churn5", "evict_healthy", "healthy_evicted"),
    ("paper-fleet-1k.crash10", "lose_crash", "crashed_in_view"),
    ("cluster-100k.trickle", "lose_crash", "crashed_in_view"),
    ("paper-fleet-1k.trickle", "evict_healthy", "healthy_evicted"),
])
def test_broken_path_comes_out_not_correct(checkout, cell, fault, number):
    done = tiny.run_cell(checkout, cell, seed=99, seconds=0.3,
                         script="benchmarks/control.py", extra=("--fault", fault))
    result = tiny.result_of(done)
    assert result["correct"] is False and result["failed"] > 0
    line = next(l for l in done.stdout.splitlines() if l.startswith(f"check {number}:"))
    assert int(line.split("value=")[1].split()[0]) > 0


# -- new cells and metrics are new files and entries ---------------------------


def test_a_new_config_traffic_and_metric_need_no_edit_to_a_file_that_is_there(tmp_path):
    where = tiny.checkout(str(tmp_path))
    before = {
        os.path.join(d, f): open(os.path.join(d, f), "rb").read()
        for d, _, files in os.walk(os.path.join(where, "benchmarks")) for f in files
    }
    config = json.load(open(os.path.join(where, "benchmarks/configs/cluster-100k.json")))
    config.update(members=300, slots=320, cohorts=2)
    json.dump(config, open(os.path.join(where, "benchmarks/configs/dummy-300.json"), "w"))
    json.dump({"kind": "closed_loop", "crashes_per_cluster": 3, "resolve": "to_decision"}, open(os.path.join(where, "benchmarks/traffic/crash3.json"), "w"))
    with open(os.path.join(where, "benchmarks/metrics/dummy_steps.py"), "w") as handle:
        handle.write("def read(run):\n    return float(run['attempted'])\n")
    bench = json.load(open(os.path.join(where, "BENCHMARK.json")))
    bench["configs"].append({"name": "dummy-300", "source": config["source"], "reduced": [],
                             "file": "benchmarks/configs/dummy-300.json", "why": "test"})
    bench["workloads"].append({"name": "dummy-300.crash3", "config": "dummy-300",
                               "traffic": "crash3", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "dummy_steps", "unit": "steps", "better": "higher",
                               "source": "program_counter", "layer": "set-up", "moves": "setup_s",
                               "workloads": ["dummy-300.crash3"]})
    for metric in bench["end_to_end"]:
        if metric["name"] == "view_changes_per_s":
            metric["workloads"].append("dummy-300.crash3")
    json.dump(bench, open(os.path.join(where, "BENCHMARK.json"), "w"))
    plain = tiny.result_of(tiny.run_cell(where, "dummy-300.crash3", seconds=0.2))
    assert plain["correct"] and set(plain["metrics"]) == {"view_changes_per_s", "setup_s"}
    traced = tiny.result_of(tiny.run_cell(where, "dummy-300.crash3", seconds=0.2, trace=1))
    assert traced["metrics"]["dummy_steps"]["value"] == traced["attempted"]
    for path, content in before.items():
        assert open(path, "rb").read() == content, path


# -- the trace reduction on a recorded chip trace ------------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(os.path.dirname(__file__), "data", "recorded_trace.json"), encoding="utf-8") as handle:
        raw = json.load(handle)
    return {
        "devices": {plane: [tuple(e) for e in events] for plane, events in raw["devices"].items()},
        "spans": [tuple(s) for s in raw["spans"]],
    }


def test_reduction_of_the_recorded_trace(recorded):
    out = trace_reduce.reduce(recorded)
    assert out["busy_s"] == pytest.approx(0.112358451, abs=1e-9)
    assert out["top_ops"][0] == ["fusion.17 s32[1025000]", pytest.approx(0.008803848)]
    # eight rounds of one commit, each with one call of the Mosaic kernel
    kernel = "delivery_new_bits_pallas.1 u32[64,102528]"
    assert out["op_calls"][kernel] == 8 and out["op_s"][kernel] == pytest.approx(0.00404165)
    # a while spans its body's operations: what is left to it is next to nothing
    assert out["op_s"]["while while.215"] < 1e-4
    assert out["idle_gaps"][0] == ["restore", pytest.approx(0.003895283)]
    assert out["span_s"]["resolve"] == pytest.approx(0.094356432)
    assert len(out["top_ops"]) == 10 and len(out["idle_gaps"]) == 10


def test_reduction_takes_children_out_and_names_gaps():
    loaded = {
        "devices": {"/device:TPU:0": [
            ("while", 0, 100), ("a", 10, 30), ("b", 50, 40), ("c", 200, 50),
        ]},
        "spans": [("resolve", 0, 120), ("check", 120, 100)],
    }
    out = trace_reduce.reduce(loaded)
    assert out["busy_s"] == pytest.approx(150e-9)
    assert out["op_s"] == {"c": 50e-9, "b": 40e-9, "a": 30e-9, "while": 30e-9}
    assert out["idle_gaps"] == [["check", 100e-9]]


def test_a_trace_without_a_device_plane_is_an_error(tmp_path):
    with pytest.raises(RuntimeError, match="no .xplane.pb"):
        trace_reduce.load(str(tmp_path))


# -- the plain reference --------------------------------------------------------


def test_membership_model_against_a_hand_made_schedule():
    initial = np.zeros((2, 8), dtype=bool)
    initial[:, :5] = True
    model = membership_model.MembershipModel(initial)
    model.apply(np.array([[0, 1], [0, 3], [1, 4]]), np.array([[0, 6]]))
    assert model.sizes().tolist() == [4, 4]
    view = model.expected.copy()
    assert membership_model.failures(model.compare_view(view)) == 0
    view[0, 2] = False  # a healthy member evicted
    view[1, 4] = True  # a crashed member still in the view
    view[1, 7] = True  # a slot that never joined
    assert model.compare_view(view) == {
        "healthy_evicted": 1, "crashed_in_view": 1, "strangers_in_view": 1}
    before = {"epoch": np.array([3, 3]), "config_hi": np.array([1, 1]), "config_lo": np.array([9, 9])}
    good = {"epoch": np.array([4, 4]), "config_hi": np.array([1, 1]), "config_lo": np.array([8, 7])}
    assert membership_model.failures(model.compare_epochs(before, good)) == 0
    stuck = {"epoch": np.array([3, 6]), "config_hi": np.array([1, 1]), "config_lo": np.array([9, 9])}
    assert model.compare_epochs(before, stuck) == {
        "view_changes_out_of_range": 2, "config_id_not_advanced": 1}
    with pytest.raises(ValueError):
        model.apply(np.array([[0, 1]]), np.zeros((0, 2), dtype=int))  # crashed twice
    model.reset()
    assert model.sizes().tolist() == [5, 5]
