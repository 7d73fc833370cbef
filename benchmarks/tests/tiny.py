"""A throw-away checkout of the benchmark at sizes a CPU test can hold.

The four cells keep their names, traffic files, generators and readers; only
the two configuration files are swapped for tiny ones. The program is found
through ``PYTHONPATH``, as a checkout of the repository finds it beside the
benchmark.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = {
    "cluster-100k": {"members": 4000, "slots": 4400, "cohorts": 4},
    "paper-fleet-1k": {"tenants": 6, "members": 200, "slots": 200, "cohorts": 4},
}


def checkout(where: str) -> str:
    """Copy ``BENCHMARK.json`` and ``benchmarks/`` into ``where``, tiny."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), where)
    shutil.copytree(
        os.path.join(REPO, "benchmarks"), os.path.join(where, "benchmarks"),
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    for name, sizes in TINY.items():
        path = os.path.join(where, "benchmarks", "configs", name + ".json")
        with open(path, encoding="utf-8") as handle:
            config = json.load(handle)
        config.update(sizes)
        with open(path, "w", encoding="utf-8") as handle:
            json.dump(config, handle)
    return where


def run_cell(where: str, workload: str, *, seed: int = 7, seconds: float = 1.0, trace: int = 0,
             platform: str | None = "cpu", script: str = "benchmarks/run.py", extra=(),
             pythonpath: str = REPO):
    """One run of the harness in ``where``; returns the completed process."""
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = pythonpath
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(where, ".jax_cache")
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    return subprocess.run(
        [sys.executable, script, "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), *extra],
        cwd=where, env=env, capture_output=True, text=True, timeout=600,
    )


def result_of(done) -> dict:
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])
