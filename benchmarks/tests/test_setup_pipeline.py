"""The four set-up metrics that the program accounts for itself
(``benchmarks/setup_pipeline.py``), rehearsed on the CPU: a traced tiny run of
one cluster cell and of one fleet cell reports them, and a program that keeps
no such sums (the parent of the PR that brought them) leaves them out of the
result line. Run with ``python -m pytest benchmarks/tests/test_setup_pipeline.py -q``
from the root.
"""

from __future__ import annotations

import json
import os
import re

import pytest

from benchmarks.tests import tiny

BENCH = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json"), encoding="utf-8"))
NEW = ("setup_trace_s", "setup_lower_s", "setup_load_s", "setup_create_s")
CELLS = [cell["name"] for cell in BENCH["workloads"]]

#: The harness's ``run.py`` over a program whose collector is the parent's:
#: five keys in ``compile_snapshot()`` and no ``setup_snapshot``.
PARENT_STUB = '''
import os, sys, time
T0 = time.perf_counter()
sys.path.insert(0, os.getcwd())
from rapid_tpu.utils import engine_telemetry
KEPT = ("compiles", "compile_ms", "persistent_cache_hits", "persistent_cache_misses", "cache_requests")
whole = engine_telemetry.compile_snapshot
engine_telemetry.compile_snapshot = lambda: {key: whole()[key] for key in KEPT}
del engine_telemetry.setup_snapshot
from benchmarks import harness
sys.exit(harness.main(sys.argv[1:], T0))
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    where = tiny.checkout(str(tmp_path_factory.mktemp("bench_setup")))
    with open(os.path.join(where, "parent_stub.py"), "w", encoding="utf-8") as handle:
        handle.write(PARENT_STUB)
    return where


def test_the_four_metrics_are_additions_on_every_cells_list():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"][-4:]] == list(NEW)
    for name in NEW:
        assert entries[name] == {
            "name": name, "unit": "s", "better": "lower", "source": "program_counter",
            "layer": "set-up", "moves": "setup_s", "workloads": CELLS,
        }
        assert os.path.exists(os.path.join(tiny.REPO, "benchmarks", "metrics", name + ".py"))


@pytest.mark.parametrize("cell", ["cluster-100k.churn5", "paper-fleet-1k.crash10"])
def test_traced_tiny_run_reports_the_four_metrics(checkout, cell):
    done = tiny.run_cell(checkout, cell, trace=1)
    result = tiny.result_of(done)
    assert result["correct"] and result["metrics"]["compiles_in_window"]["value"] == 0
    got = {name: result["metrics"][name] for name in NEW}
    assert all(entry["unit"] == "s" and entry["value"] >= 0 for entry in got.values())
    trace_s, lower_s, load_s, create_s = (got[name]["value"] for name in NEW)
    # The window line prints set-up to a tenth of a second.
    setup_s = float(re.search(r"set-up ([0-9.]+) s", done.stdout).group(1)) + 0.05
    # Every second of the pipeline is counted once, and all of it and all of
    # the constructors' blocks lie before the window. (The constructors' own
    # tracing and loading is inside both, so the four do not add up.)
    assert 0 < trace_s + lower_s + load_s <= setup_s
    assert 0 < create_s <= result["metrics"]["state_build_s"]["value"] <= setup_s


def test_a_program_without_the_sums_leaves_the_metrics_out(checkout):
    done = tiny.run_cell(checkout, "cluster-100k.churn5", trace=1, script="parent_stub.py")
    result = tiny.result_of(done)
    assert result["correct"]
    assert not set(NEW) & set(result["metrics"])
    assert {"state_build_s", "warmup_programs", "compiles_in_window"} <= set(result["metrics"])
