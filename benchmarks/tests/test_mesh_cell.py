"""``cluster-10m.crash1`` rehearsed on the CPU: four virtual devices, a few
thousand members. The cell keeps its name, its traffic file, its generator
and its readers; this file swaps the configuration's size for a tiny one in
a throw-away checkout of its own (``tiny.py`` knows the one-chip cells only)
and gives the process four host devices. Run with
``python -m pytest benchmarks/tests/test_mesh_cell.py -q`` from the root.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmarks.tests import tiny

CELL = "cluster-10m.crash1"
BENCH = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8"))
TINY = {"members": 4000, "slots": 4000}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    where = tiny.checkout(str(tmp_path_factory.mktemp("bench_mesh")))
    path = os.path.join(where, "benchmarks", "configs", "cluster-10m.json")
    with open(path, encoding="utf-8") as handle:
        config = json.load(handle)
    config.update(TINY)
    with open(path, "w", encoding="utf-8") as handle:
        json.dump(config, handle)
    return where


def run_cell(where: str, *, seed: int, trace: int = 0, devices: int = 4,
             script: str = "benchmarks/run.py", extra=()):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(
        PYTHONPATH=tiny.REPO, JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=os.path.join(where, ".jax_cache"),
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
    )
    return subprocess.run(
        [sys.executable, script, "--workload", CELL, "--seed", str(seed),
         "--seconds", "0.5", "--trace", str(trace), *extra],
        cwd=where, env=env, capture_output=True, text=True, timeout=600,
    )


def test_the_cell_is_the_only_one_on_four_chips_and_its_files_are_there():
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "crash1"
    assert [c["name"] for c in BENCH["workloads"] if c["chips"] == 4] == [CELL]
    config = json.load(open(os.path.join(tiny.REPO, "benchmarks/configs/cluster-10m.json")))
    assert config["members"] == 10_000_000 and config["reduced"] == [] and not config["use_pallas"]
    nodes = config["mesh"]["shape"][1]
    assert config["slots"] % nodes == 0 and config["slots"] - config["members"] < nodes
    assert config["cohorts"] % config["mesh"]["shape"][0] == 0
    hundred_k = json.load(open(os.path.join(tiny.REPO, "benchmarks/configs/cluster-100k.json")))
    assert config["guarantees"] == hundred_k["guarantees"]
    traffic = json.load(open(os.path.join(tiny.REPO, "benchmarks/traffic/crash1.json")))
    assert traffic["crash_share"] == 0.01 and traffic["join_share"] == 0
    assert traffic["resolve"] == "to_decision" and "plan_cycle" not in traffic


def test_untraced_run_prints_the_contracts_line(checkout):
    result = tiny.result_of(run_cell(checkout, seed=4294967301))
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"commit_ms_p50", "setup_s"}
    assert result["device"]["count"] == 4


def test_traced_run_reports_the_cells_per_layer_metrics(checkout):
    done = run_cell(checkout, seed=11, trace=1)
    result = tiny.result_of(done)
    wanted = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) == wanted
    assert 0 < result["metrics"]["collective_share.commit"]["value"] < 100
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["metrics"]["rounds_per_commit.commit"]["value"] >= 1
    assert "check compiles_in_window: value=0 limit=0" in done.stdout


@pytest.mark.parametrize("fault,number", [
    ("evict_healthy", "healthy_evicted"), ("lose_crash", "crashed_in_view"),
])
def test_broken_path_comes_out_not_correct(checkout, fault, number):
    done = run_cell(checkout, seed=99, script="benchmarks/control.py", extra=("--fault", fault))
    result = tiny.result_of(done)
    assert result["correct"] is False and result["failed"] > 0
    line = next(l for l in done.stdout.splitlines() if l.startswith(f"check {number}:"))
    assert int(line.split("value=")[1].split()[0]) > 0


def test_too_few_devices_means_no_result(checkout):
    done = run_cell(checkout, seed=5, devices=2)
    assert done.returncode != 0 and '"correct"' not in done.stdout


def test_collective_share_reads_the_trace_by_name():
    from benchmarks.metrics import collective_share

    assert collective_share.read({}) is None
    trace = {"op_s": {"fusion.1 s32[8]": 3.0, "all-reduce.2 u32[]": 0.5,
                      "all-gather-start.1 s32[4]": 0.25, "collective-permute-done.7": 0.25}}
    assert collective_share.read({"trace": trace}) == pytest.approx(25.0)
    assert collective_share.read({"trace": {"op_s": {"fusion.1 s32[8]": 3.0}}}) is None
