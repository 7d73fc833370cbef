"""Static-analysis build gate.

The reference fails its build on error-prone (-Werror), findbugs, and
checkstyle violations (root pom.xml + build-common/). This environment ships
no ruff/mypy, so the equivalent gate is enforced here with stdlib ``ast``
checks over the whole source tree, run as part of the ordinary test session:
a violation fails the build the same way checkstyle fails the reference's.

Checks: unused module imports, bare ``except:`` clauses, mutable default
arguments, and two observability-discipline rules over ``rapid_tpu/`` only:
no bare ``print()`` for runtime diagnostics (the library speaks through
``logging``, ``Metrics``, and the flight recorder — exposition that a
production deployment can route; stdout it cannot), and every
flight-recorder ``record()`` call site names its event via the registered
``EventName`` enum (free-form strings would silently fork the event
vocabulary and break traceview's causal phase ordering). The resolution
tier — undefined names, call-signature conformance — lives in
tools/staticcheck.py, gated by tests/test_staticcheck.py (the error-prone
analog; this file is the checkstyle analog).
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

from staticcheck import iter_files as _py_files  # noqa: E402  — one root list for both tiers


def _parse(path: Path):
    return ast.parse(path.read_text(), filename=str(path))


def test_no_unused_imports():
    offenders = []
    for path in _py_files():
        tree = _parse(path)
        imports = []  # (lineno, bound_name)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    bound = alias.asname or alias.name.split(".")[0]
                    imports.append((node.lineno, bound))
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    bound = alias.asname or alias.name
                    imports.append((node.lineno, bound))
        used = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
        # Re-exports: an __all__ entry (or any other string constant EXACTLY
        # equal to the name) counts as a use. Substring matching would let a
        # docstring containing "host" excuse an unused `import os`.
        exact_strings = {
            n.value
            for n in ast.walk(tree)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)
        }
        for lineno, name in imports:
            if name in used or name in exact_strings:
                continue
            offenders.append(f"{path.relative_to(REPO)}:{lineno}: unused import {name!r}")
    assert not offenders, "\n".join(offenders)


def test_no_bare_except():
    offenders = []
    for path in _py_files():
        for node in ast.walk(_parse(path)):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                offenders.append(f"{path.relative_to(REPO)}:{node.lineno}: bare except")
    assert not offenders, "\n".join(offenders)


def test_library_has_no_bare_print():
    """rapid_tpu/ must not print() runtime diagnostics: the structured
    channels (logging, Metrics, FlightRecorder, the exposition snapshot) are
    scrapeable and mergeable; stdout is neither. Examples/tools/tests are
    exempt — a CLI's job is to print."""
    offenders = []
    for path in _py_files(("rapid_tpu",)):
        for node in ast.walk(_parse(path)):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "print"
            ):
                offenders.append(
                    f"{path.relative_to(REPO)}:{node.lineno}: bare print() — "
                    "use logging / Metrics / FlightRecorder"
                )
    assert not offenders, "\n".join(offenders)


def test_recorder_events_come_from_registered_enum():
    """Every flight-recorder record() call site in rapid_tpu/ must name its
    event as ``EventName.<member>`` — the registered vocabulary traceview's
    causal phase ranking is defined over. (Matched: any ``*.record(...)`` or
    ``self._record(...)`` call; ``Metrics.record_ms`` has a different
    attribute name and is not caught.)"""
    from rapid_tpu.utils.flight_recorder import EventName

    offenders = []
    for path in _py_files(("rapid_tpu",)):
        for node in ast.walk(_parse(path)):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("record", "_record")
            ):
                continue
            args = list(node.args)
            name_arg = args[0] if args else next(
                (kw.value for kw in node.keywords if kw.arg == "name"), None
            )
            ok = (
                isinstance(name_arg, ast.Attribute)
                and isinstance(name_arg.value, ast.Name)
                and name_arg.value.id == "EventName"
                and name_arg.attr in EventName.__members__
            )
            # A record() call forwarding an already-checked EventName
            # parameter (the cut detector's _record helper body) is fine.
            forwards = isinstance(name_arg, ast.Name) and name_arg.id == "name"
            if not (ok or forwards):
                offenders.append(
                    f"{path.relative_to(REPO)}:{node.lineno}: record() event "
                    "must be an EventName member"
                )
    assert not offenders, "\n".join(offenders)


def test_ledger_events_come_from_registered_vocabulary():
    """Every run-ledger ``emit()`` call site in the library, bench.py, and
    tools/ must name its event as ``LedgerEvent.<member>`` — the registered
    vocabulary tools/perfview.py's timeline rendering (and the bench's
    per-stage budgets) are defined over. Mirror of the flight-recorder
    EventName rule above; the resolution-tier twin lives in
    tools/analysis/ledger.py (check_ledger) so the CLI gate catches it too.
    Only files importing rapid_tpu.utils.ledger are in scope — unrelated
    ``emit`` methods are not."""
    from staticcheck import check_ledger

    offenders = []
    for path in _py_files(("rapid_tpu", "bench.py", "tools")):
        offenders.extend(str(f) for f in check_ledger(path))
    assert not offenders, "\n".join(offenders)


def test_protocol_reads_no_wall_clock():
    """The clock-disciplined packages (rapid_tpu/protocol/,
    rapid_tpu/monitoring/ — failure detectors are timing consumers too —
    and, since ISSUE 15, rapid_tpu/serving/ — the supervision tier's
    deadline/backoff decisions must replay under an injected clock) must
    not read wall clocks directly (time.time, time.time_ns, datetime.now,
    ...): the clock is injected (utils/clock.py, the Metrics registry's
    now_ms source, the serving drivers' clock= parameter), which is what
    keeps phase timings correct under simulated time and fault drills
    deterministic. The resolution-tier check lives in
    tools/analysis/clocks.py (check_clock_injection) so the CLI gate
    catches it too; this test runs it as part of the ordinary session.
    The tree is currently clean — keep it that way."""
    from staticcheck import check_clock_injection

    offenders = []
    for path in _py_files(
        ("rapid_tpu/protocol", "rapid_tpu/monitoring", "rapid_tpu/serving")
    ):
        offenders.extend(str(f) for f in check_clock_injection(path))
    assert not offenders, "\n".join(offenders)


def test_clock_injection_covers_the_serving_tier():
    """ISSUE 15: the serving supervision tier's timing reads are
    clock-disciplined too — a wall-clock read in a serving module is a
    finding (the wedge-deadline decision path must be injectable), while
    the same source outside the disciplined prefixes stays silent."""
    import textwrap

    from staticcheck import REPO as SC_REPO, check_clock_injection

    offending = textwrap.dedent(
        """
        import time

        def deadline_exceeded(t0, budget_ms):
            return (time.monotonic() - t0) * 1000.0 >= budget_ms
        """
    )
    inside = SC_REPO / "rapid_tpu" / "serving" / "_lint_probe.py"
    findings = check_clock_injection(inside, source=offending)
    assert [f.check for f in findings] == ["clock-injection"]
    outside = SC_REPO / "rapid_tpu" / "sim" / "_lint_probe.py"
    assert check_clock_injection(outside, source=offending) == []


def test_clock_injection_check_catches_both_spellings():
    """The rule itself must fire on both the attribute and the from-import
    spelling, and stay silent outside rapid_tpu/protocol/."""
    import textwrap

    from staticcheck import REPO as SC_REPO, check_clock_injection

    offending = textwrap.dedent(
        """
        import time
        from time import perf_counter

        def now():
            return time.time() + perf_counter()
        """
    )
    inside = SC_REPO / "rapid_tpu" / "protocol" / "_lint_probe.py"
    findings = check_clock_injection(inside, source=offending)
    assert len(findings) == 2, findings
    assert all(f.check == "clock-injection" for f in findings)
    outside = SC_REPO / "rapid_tpu" / "utils" / "_lint_probe.py"
    assert check_clock_injection(outside, source=offending) == []


def test_full_sweep_with_compiled_gate_stays_under_budget(record_property):
    """The whole-tree sweep INCLUDING the compiled-artifact families — the
    sharding AST lint, the device_program gate and the jaxpr provenance
    trace — must fit the ordinary test session: <160 s of process CPU for
    the collections (the registry compiles plus the compile-free registry
    trace; these cost real time and this budget may grow with the
    registry, the analysis-only budget must not) and <45 s for the family
    sweep itself (test_staticcheck.py's budget for the same call, and its
    readings), budgeted separately so neither can hide the other going
    superlinear. Both readings go to the junit XML (`collect_cpu_s`,
    `sweep_cpu_s`). Collection results — the facts AND the dataflow proofs —
    are cached per session, so only the FIRST sweep in a process pays
    them; the identity assertions pin that the session caches are real."""
    import time

    import staticcheck

    started = time.process_time()
    first = staticcheck.collect_facts()
    dataflow_proofs, _ = staticcheck.collect_dataflow()
    compile_s = time.process_time() - started
    record_property("collect_cpu_s", round(compile_s, 2))
    # Fresh compiles when this file runs standalone; a session-cache hit
    # when test_hlo_gate.py and test_dataflow.py ran first — the check.sh
    # ordering. The cost is pinned in BOTH orderings.
    assert compile_s < 160.0, (
        f"collections (registry + dataflow trace) used "
        f"{compile_s:.1f}s CPU (budget 160s)"
    )
    started = time.process_time()
    findings = staticcheck.run()
    sweep_s = time.process_time() - started
    record_property("sweep_cpu_s", round(sweep_s, 2))
    assert not findings, "\n".join(str(f) for f in findings)
    assert sweep_s < 45.0, (
        f"tree sweep over cached facts used {sweep_s:.1f}s CPU (budget 45s)"
    )
    assert staticcheck.collect_facts() is first  # session cache holds
    assert staticcheck.collect_dataflow()[0] is dataflow_proofs  # trace cache


def test_library_sweep_is_clean_under_all_families():
    """The per-file resolution families (incl. the dispatch and taskflow
    analyzers added with the wire-conformance tier) are clean over
    rapid_tpu/ — the library keeps its failure paths justified or narrow,
    its background tasks tracked, and its dispatch chain exhaustive. The
    whole-tree gate (with the deadcode + wire-lock tree checks) lives in
    test_staticcheck.py; this pin localizes a regression to the library."""
    import staticcheck

    findings = staticcheck.run(("rapid_tpu",))
    assert not findings, "\n".join(str(f) for f in findings)


def test_no_mutable_default_arguments():
    offenders = []
    for path in _py_files():
        for node in ast.walk(_parse(path)):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for default in [*node.args.defaults, *node.args.kw_defaults]:
                    if isinstance(default, (ast.List, ast.Dict, ast.Set)) or (
                        isinstance(default, ast.Call)
                        and isinstance(default.func, ast.Name)
                        and default.func.id in ("list", "dict", "set")
                    ):
                        offenders.append(
                            f"{path.relative_to(REPO)}:{node.lineno}: "
                            f"mutable default in {node.name}()"
                        )
    assert not offenders, "\n".join(offenders)
