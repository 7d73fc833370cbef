"""Static-analysis tier — compatible CLI/entry shim over tools/analysis/.

The analyzers grew from two check families into ten and moved into the
``tools/analysis/`` package (core driver + Finding model + one module per
family — see its docstring for the catalog, or ``--families``). This
module stays as the stable entry point: ``python tools/staticcheck.py
[--json] [--select ...] [--ignore ...] [--families] [--update-wire-lock]
[paths...]`` and ``import staticcheck`` both keep working, re-exporting
the package API unchanged.

Tests that retarget the analysis at a temporary tree patch
``staticcheck.core.REPO`` (the package reads it at call time).
"""

from __future__ import annotations

import sys
from pathlib import Path

# The package lives next to this shim. Resolve it regardless of how the
# shim itself was imported (`staticcheck` with tools/ on sys.path, or
# `tools.staticcheck` during the gate's own call-signature pass).
_TOOLS_DIR = str(Path(__file__).resolve().parent)
if _TOOLS_DIR not in sys.path:
    sys.path.insert(0, _TOOLS_DIR)

from analysis import core  # noqa: E402
from analysis import (  # noqa: E402,F401 — re-exported API surface
    ALL_CHECK_NAMES,
    CLOCK_DISCIPLINE_PREFIXES,
    CONCURRENCY_PREFIXES,
    DEFAULT_ROOTS,
    DETERMINISM_PREFIXES,
    DISPATCH_PREFIXES,
    FAMILIES,
    Finding,
    LEDGER_PREFIXES,
    LOCK_REL,
    SHARDING_PREFIXES,
    STREAM_PREFIXES,
    TASKFLOW_PREFIXES,
    TELEMETRY_LANE_FIELDS,
    TELEMETRY_PREFIXES,
    TRACE_SAFETY_PREFIXES,
    WIRE_FILES,
    check_call_signatures,
    check_chaosvocab,
    check_clock_injection,
    check_compiled_programs,
    check_concurrency,
    check_dataflow,
    check_dataflow_proofs,
    check_dead_definitions,
    check_determinism,
    check_device_program,
    check_dispatch,
    check_lane_mirror,
    check_ledger,
    check_partition_specs,
    check_sharding,
    check_taskflow,
    check_telemetry,
    check_trace_safety,
    check_undefined_names,
    check_wire_lock,
    check_wire_schema,
    collect_dataflow,
    collect_facts,
    iter_files,
    main,
    run,
    update_wire_lock,
)

#: Snapshot for path construction by callers; behavior-affecting resolution
#: reads ``core.REPO`` at call time (patch that one in tests).
REPO = core.REPO

__all__ = [
    "ALL_CHECK_NAMES",
    "CLOCK_DISCIPLINE_PREFIXES",
    "CONCURRENCY_PREFIXES",
    "DEFAULT_ROOTS",
    "DETERMINISM_PREFIXES",
    "DISPATCH_PREFIXES",
    "FAMILIES",
    "Finding",
    "LEDGER_PREFIXES",
    "LOCK_REL",
    "REPO",
    "SHARDING_PREFIXES",
    "STREAM_PREFIXES",
    "TASKFLOW_PREFIXES",
    "TELEMETRY_LANE_FIELDS",
    "TELEMETRY_PREFIXES",
    "TRACE_SAFETY_PREFIXES",
    "WIRE_FILES",
    "check_call_signatures",
    "check_chaosvocab",
    "check_clock_injection",
    "check_compiled_programs",
    "check_concurrency",
    "check_dataflow",
    "check_dataflow_proofs",
    "check_dead_definitions",
    "check_determinism",
    "check_device_program",
    "check_dispatch",
    "check_lane_mirror",
    "check_ledger",
    "check_partition_specs",
    "check_sharding",
    "check_taskflow",
    "check_telemetry",
    "check_trace_safety",
    "check_undefined_names",
    "check_wire_lock",
    "check_wire_schema",
    "collect_dataflow",
    "collect_facts",
    "core",
    "iter_files",
    "main",
    "run",
    "update_wire_lock",
]

if __name__ == "__main__":
    sys.path.insert(0, str(core.REPO))
    from rapid_tpu.utils.platform import force_platform

    # The gate never takes the chip: the device_program family compiles
    # the registered engine entrypoints under the same forced 8-device CPU
    # mesh the test session uses.
    force_platform("cpu", n_host_devices=8)
    sys.exit(main(sys.argv[1:]))
