"""Resolution-grade static analysis for this repo, as a package.

The reference fails its build on error-prone (-Werror), findbugs, and
checkstyle findings (root pom.xml + build-common/); the AST style gate in
tests/test_lint.py covers the checkstyle analog, and this package plays the
error-prone role — the class of checks that needs RESOLUTION, not just
syntax. This environment ships no ruff/mypy/pyflakes, so the tier is built
on the stdlib (``ast``, ``symtable``, ``inspect``).

Check families (one module each; ``core`` owns the driver/CLI/Finding):

1. ``names``        — undefined names (symtable scope resolution)
2. ``signatures``   — call-signature conformance vs imported runtime modules
3. ``clocks``       — clock-injection discipline (protocol + monitoring)
4. ``deadcode``     — dead module-level definitions (tree-wide liveness)
5. ``concurrency``  — asyncio guarded-by discipline, interleaving hazards,
                      lock re-entrancy (protocol + messaging)
6. ``trace_safety`` — JAX jit purity/staticness (ops)
7. ``wire_schema``  — the four hand-kept wire-schema mirrors cross-checked
                      and frozen in ``wire.lock.json`` (types/codec/proto)
8. ``dispatch``     — RapidRequest dispatch exhaustiveness, shadowed arms,
                      and response return types (protocol)
9. ``taskflow``     — async failure-path hygiene: leaked tasks, swallowed
                      exceptions, cancellation swallows, unawaited
                      coroutines (whole library)
10. ``determinism`` — no unseeded randomness in the library: every rng is
                      injectable or identity-seeded, so simulated chaos
                      runs (rapid_tpu/sim) are pure functions of one seed
11. ``ledger``      — run-ledger vocabulary discipline (LedgerEvent /
                      STAGE_NAMES)
12. ``device_program`` — the compiled artifact itself: every registered
                      jitted engine entrypoint compiled on a forced
                      8-device CPU mesh and its collectives/transfers/
                      donation/memory facts read live (no committed
                      number: an unknown dtype, an unwaived dropped
                      donation, a cross-tenant collective or a host
                      transfer is a finding)
13. ``sharding``    — source seams that produce bad compiled programs:
                      partition-spec coverage of the engine state pytree,
                      host syncs inside traced hot paths AND anywhere in
                      the streaming pipeline (rapid_tpu/serving — every
                      blocking read there is a declared fetch boundary or
                      a finding), jit callsites that forget buffer
                      donation or invite retraces (ops/models/parallel)
14. ``telemetry``   — the TelemetryLanes field mirror and the declared
                      fetch boundaries of the lanes and the trace ring
15. ``chaosvocab``  — FaultEvent kinds, scenario families and the chaosrun
                      CLI against the registered registries
16. ``dataflow``    — jaxpr lane provenance of the same registry's live
                      trace: observer silence and fleet tenant isolation

``staticcheck --families`` prints this catalog; ``--update-wire-lock``
regenerates the one lockfile (the wire format peers must agree on) after
an intentional schema change.

Shared philosophy: conservative resolution, zero-false-positive findings,
skip-don't-guess. Run via ``python tools/staticcheck.py`` (the compatible
CLI shim) or the build gate in tests/test_staticcheck.py.
"""

from __future__ import annotations

from . import core
from .chaosvocab import check_chaosvocab
from .clocks import CLOCK_DISCIPLINE_PREFIXES, check_clock_injection
from .concurrency import CONCURRENCY_PREFIXES, check_concurrency
from .core import (
    ALL_CHECK_NAMES,
    DEFAULT_ROOTS,
    FAMILIES,
    Finding,
    iter_files,
    main,
    run,
)
from .dataflow import (
    check_dataflow,
    check_dataflow_proofs,
    collect_dataflow,
)
from .deadcode import check_dead_definitions
from .determinism import DETERMINISM_PREFIXES, check_determinism
from .device_program import (
    check_compiled_programs,
    check_device_program,
    collect_facts,
)
from .dispatch import DISPATCH_PREFIXES, check_dispatch
from .ledger import LEDGER_PREFIXES, check_ledger
from .names import check_undefined_names
from .sharding import (
    SHARDING_PREFIXES,
    STREAM_PREFIXES,
    check_partition_specs,
    check_sharding,
)
from .signatures import check_call_signatures
from .taskflow import TASKFLOW_PREFIXES, check_taskflow
from .telemetry import (
    TELEMETRY_LANE_FIELDS,
    TELEMETRY_PREFIXES,
    check_lane_mirror,
    check_telemetry,
)
from .trace_safety import TRACE_SAFETY_PREFIXES, check_trace_safety
from .wire_schema import (
    LOCK_REL,
    WIRE_FILES,
    check_wire_lock,
    check_wire_schema,
    update_wire_lock,
)

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
