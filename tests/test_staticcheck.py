"""Build gate for the resolution-tier static analysis (tools/staticcheck,
backed by the tools/analysis/ package).

Two halves, matching how the reference treats error-prone: the whole tree
must be finding-free (the gate), and the analyzer itself must demonstrably
catch the defect classes it claims — a gate that never bites is
indistinguishable from no gate. The seeded corpus under
tests/data/lint_corpus/ (one file per defect class, expectations embedded
as ``# expect: <check>`` markers) is the second half for the concurrency
and trace-safety families.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

import pytest  # noqa: E402

import staticcheck  # noqa: E402

CORPUS = Path(__file__).resolve().parent / "data" / "lint_corpus"


def _undefined(src: str):
    return staticcheck.check_undefined_names(
        Path("fixture.py"), textwrap.dedent(src)
    )


def test_undefined_name_in_error_branch_is_caught():
    findings = _undefined(
        """
        import os

        def f(a):
            if a:
                return os.sep
            raise RuntimeError(mesage)  # typo: never executed by tests
        """
    )
    assert [f.check for f in findings] == ["undefined-name"]
    assert "mesage" in findings[0].message


def test_global_decl_assignment_binds_at_module_scope():
    findings = _undefined(
        """
        def setup(value):
            global _CACHE
            _CACHE = value

        def read():
            return _CACHE  # bound only via setup()'s global decl
        """
    )
    assert findings == []


def test_class_and_comprehension_scopes_resolve():
    findings = _undefined(
        """
        BASE = 2

        class C:
            x = BASE
            def m(self):
                return [BASE + i for i in range(self.x)]

        lam = lambda z: z + BASE
        """
    )
    assert findings == []


def test_star_import_is_flagged_not_skipped():
    findings = _undefined("from os.path import *\n")
    assert [f.check for f in findings] == ["star-import"]


def _caller_findings(tmp_path, monkeypatch, name: str, callee_src: str, caller_src: str):
    """Materialize a callee+caller module pair under a private root and run
    the call-conformance check on the caller."""
    (tmp_path / f"{name}_callee.py").write_text(textwrap.dedent(callee_src))
    caller = tmp_path / f"{name}_caller.py"
    caller.write_text(textwrap.dedent(caller_src))
    monkeypatch.setattr(staticcheck.core, "REPO", tmp_path)
    monkeypatch.syspath_prepend(str(tmp_path))
    return staticcheck.check_call_signatures(caller)


def test_wrong_kwarg_and_arity_are_caught(tmp_path, monkeypatch):
    findings = _caller_findings(
        tmp_path, monkeypatch, "sigs",
        """
        def encode(message, *, deadline_ms=100):
            return message, deadline_ms
        """,
        """
        import sigs_callee

        def ok():
            return sigs_callee.encode("m", deadline_ms=5)

        def typo():
            return sigs_callee.encode("m", deadlne_ms=5)

        def arity():
            return sigs_callee.encode("m", "extra")
        """,
    )
    assert [f.check for f in findings] == ["call-signature", "call-signature"]
    assert "deadlne_ms" in findings[0].message
    assert "too many positional" in findings[1].message


def test_stale_module_attribute_is_caught(tmp_path, monkeypatch):
    findings = _caller_findings(
        tmp_path, monkeypatch, "attr",
        "def current(): return 1\n",
        """
        import attr_callee

        def f():
            return attr_callee.renamed_away()
        """,
    )
    assert [f.check for f in findings] == ["missing-attribute"]
    assert "renamed_away" in findings[0].message


def test_shadowed_and_dynamic_call_sites_are_skipped(tmp_path, monkeypatch):
    findings = _caller_findings(
        tmp_path, monkeypatch, "shadow",
        "def g(a, b): return a + b\n",
        """
        import shadow_callee
        from shadow_callee import g

        def shadowed(g):
            return g(1, 2, 3, 4)  # parameter, not the module-level g

        def splat(args):
            return g(*args)  # dynamic shape: must not be judged

        def lam():
            return (lambda g: g(9, 9, 9))(len)

        def comp(items):
            return [g for g in items if g]
        """,
    )
    assert findings == []


def test_str_target_bindings_and_class_bodies_shadow(tmp_path, monkeypatch):
    # Bindings whose AST target is a plain string (except-as, match capture)
    # and class-body-level bindings must shadow module-level callables; each
    # of these produced a spurious build-failing finding before being
    # handled.
    findings = _caller_findings(
        tmp_path, monkeypatch, "strbind",
        "def handle(a, b): return a, b\n",
        """
        from strbind_callee import handle

        def except_as():
            try:
                return handle(1, 2)
            except ValueError as handle:
                return handle(0)  # the exception object, not the import

        def match_capture(x):
            match x:
                case [handle]:
                    return handle(9)
                case {**handle}:
                    return handle()
            return None

        class Uses:
            def handle(self):
                return None
            value = handle(None)  # class-local binding wins in the body
        """,
    )
    assert findings == []


def test_missing_root_fails_loudly():
    # A typo'd or renamed root must error, not shrink coverage to zero.
    with pytest.raises(FileNotFoundError, match="no_such_root"):
        list(staticcheck.iter_files(["no_such_root"]))


def test_finding_points_at_the_offending_read():
    findings = _undefined(
        """
        def f(a):


            return mesage
        """
    )
    assert [f.lineno for f in findings] == [5]  # the read, not `def f` (2)


def _dead_defs(tmp_path):
    import ast

    contributions = [
        (ast.parse(p.read_text()), p.name) for p in sorted(tmp_path.glob("*.py"))
    ]
    return staticcheck.check_dead_definitions(contributions)


def test_dead_definition_is_caught(tmp_path):
    (tmp_path / "mod_a.py").write_text(textwrap.dedent(
        """
        def used(): return 1
        def never_called(): return 2
        class Orphan: pass
        def lonely_recursive():
            return lonely_recursive()  # self-reference must not keep it alive
        STALE_TABLE = {"a": 1}
        RETRY = lambda n: RETRY(n - 1)  # self-mention must not keep it alive
        """
    ))
    (tmp_path / "mod_b.py").write_text("from mod_a import used\nprint(used())\n")
    assert sorted(f.message for f in _dead_defs(tmp_path)) == [
        "module-level 'Orphan' is referenced nowhere in the tree",
        "module-level 'RETRY' is referenced nowhere in the tree",
        "module-level 'STALE_TABLE' is referenced nowhere in the tree",
        "module-level 'lonely_recursive' is referenced nowhere in the tree",
        "module-level 'never_called' is referenced nowhere in the tree",
    ]
    # The bare re-export import did NOT count as the use — mod_b calling
    # used() did. Export padding cannot hide dead code:
    (tmp_path / "mod_b.py").write_text(
        "from mod_a import never_called\n__all__ = ['never_called']\n"
    )
    assert any("never_called" in f.message for f in _dead_defs(tmp_path))


def test_dead_definition_liveness_channels(tmp_path):
    # The ways a def stays alive without a plain call: pytest collection
    # (test_/Test*), fixture-by-parameter-name, identifiers inside
    # code-looking strings (subprocess job payloads), and entry points.
    (tmp_path / "mod.py").write_text(textwrap.dedent(
        '''
        def my_fixture(): return 3
        def job_callee(): return 4
        def main(): return 5
        class TestThings:
            def helper(self): pass
        def test_stuff(my_fixture):
            return my_fixture
        JOB = """
        from mod import job_callee
        job_callee()
        """
        print(JOB)
        '''
    ))
    assert _dead_defs(tmp_path) == []


def test_an_autouse_fixture_is_live_and_a_plain_unused_one_is_not(tmp_path):
    # pytest applies an autouse fixture to every test of its scope: no
    # test names it, and it is no dead definition for that.
    (tmp_path / "mod.py").write_text(textwrap.dedent(
        '''
        import pytest
        from pytest import fixture
        @pytest.fixture(scope="module", autouse=True)
        def release_maps(): yield
        @fixture(autouse=True)
        def also_live(): yield
        @pytest.fixture(autouse=False)
        def named_by_nobody(): yield
        '''
    ))
    assert sorted(f.message for f in _dead_defs(tmp_path)) == [
        "module-level 'named_by_nobody' is referenced nowhere in the tree",
    ]


def test_dead_definition_sees_getattr_and_fstring_references(tmp_path):
    # ISSUE 19 regression: a definition consumed only via
    # getattr(obj, "name") or named inside an f-string fragment is live.
    (tmp_path / "mod.py").write_text(textwrap.dedent(
        '''
        def fd_hist_decode(): return 1
        def config_digest(): return 2
        def truly_dead(): return 3
        def probe(state, name):
            handler = getattr(state, "fd_hist_decode")
            return f"lane config_digest={handler(name)}"
        print(probe)
        '''
    ))
    assert sorted(f.message for f in _dead_defs(tmp_path)) == [
        "module-level 'truly_dead' is referenced nowhere in the tree",
    ]


def test_narrowed_roots_skip_liveness(tmp_path, monkeypatch):
    # A per-file/per-dir CLI run must not report cross-root consumers'
    # definitions as dead: liveness only runs on full-tree invocations.
    (tmp_path / "only.py").write_text("def consumed_elsewhere(): return 1\n")
    monkeypatch.setattr(staticcheck.core, "REPO", tmp_path)
    monkeypatch.syspath_prepend(str(tmp_path))
    findings = staticcheck.run([str(tmp_path / "only.py")])
    assert findings == []


def test_whole_tree_is_finding_free(record_property):
    # The gate itself: resolution-tier findings fail the build exactly the
    # way error-prone fails the reference's. All sixteen check families
    # run — including the compiled-program gate (device_program) and the
    # jaxpr provenance gate (dataflow), whose entrypoint compiles/traces
    # are collected ONCE per process; pre-warm the session caches here so
    # this budget pins the ANALYSIS cost, not the compile cost
    # (tests/test_lint.py budgets the compile-inclusive sweep
    # separately). Process CPU time, not wall-clock, and still a number
    # that the machine moves: the same sweep reads 6-7 s in an idle
    # process, 10 s as a pytest session's first (it imports every module of
    # the tree, the test files through pytest's rewriting hook) and
    # 12.6-15.4 s under tier-1's six workers (PR 47's readings: CHANGES).
    # The budget is three times the highest of them, so that it fails on
    # an analyzer going superlinear and not on a loaded machine; the
    # reading goes to the junit XML (`sweep_cpu_s`) on every run.
    import time

    staticcheck.collect_facts()  # session-shared; test_hlo_gate.py pins it
    staticcheck.collect_dataflow()  # session-shared; test_dataflow.py pins it
    started = time.process_time()
    findings = staticcheck.run()
    elapsed = time.process_time() - started
    record_property("sweep_cpu_s", round(elapsed, 2))
    assert not findings, "\n".join(str(f) for f in findings)
    assert elapsed < 45.0, (
        f"sixteen-family tree sweep used {elapsed:.1f}s CPU (budget 45s)"
    )


# ---------------------------------------------------------------------------
# Driver robustness: syntax errors are findings, not crashes
# ---------------------------------------------------------------------------


def test_syntax_error_is_finding_not_crash(tmp_path, monkeypatch):
    # One unparseable file must report itself and leave the rest of the
    # tree analyzed (the old driver crashed the whole gate with a
    # traceback on the first broken file).
    (tmp_path / "broken.py").write_text("def f(:\n    return 0\n")
    (tmp_path / "good.py").write_text("def g():\n    return mesage\n")
    monkeypatch.setattr(staticcheck.core, "REPO", tmp_path)
    monkeypatch.syspath_prepend(str(tmp_path))
    findings = staticcheck.run([str(tmp_path)])
    assert sorted(f.check for f in findings) == ["syntax-error", "undefined-name"]
    syntax = next(f for f in findings if f.check == "syntax-error")
    assert syntax.path.endswith("broken.py") and syntax.lineno == 1


# ---------------------------------------------------------------------------
# Seeded lint corpus: one file per defect class, expectations embedded as
# `# expect: <check>` markers — exactly those findings and nothing else
# ---------------------------------------------------------------------------

_EXPECT_RE = re.compile(r"#\s*expect:\s*([a-z][a-z-]*)")

#: corpus file -> (pretend repo path, check function name). The pretend
#: path places the source inside the prefix each analyzer guards, the way
#: the clock-injection tests in test_lint.py do. The wire_schema corpus
#: files keep all schema mirrors as miniatures in one module (tree sweeps
#: merge the three real mirror files the same way).
_CORPUS_CHECKERS = {
    "unguarded_mutation.py": ("rapid_tpu/protocol/_corpus.py", "check_concurrency"),
    "interleaving_hazard.py": ("rapid_tpu/protocol/_corpus.py", "check_concurrency"),
    "lock_reentrancy.py": ("rapid_tpu/protocol/_corpus.py", "check_concurrency"),
    "clean_concurrency.py": ("rapid_tpu/protocol/_corpus.py", "check_concurrency"),
    "jit_side_effect.py": ("rapid_tpu/ops/_corpus.py", "check_trace_safety"),
    "jit_traced_branch.py": ("rapid_tpu/ops/_corpus.py", "check_trace_safety"),
    "clean_trace_safety.py": ("rapid_tpu/ops/_corpus.py", "check_trace_safety"),
    "missing_decode_arm.py": ("rapid_tpu/messaging/_corpus.py", "check_wire_schema"),
    "tag_reuse.py": ("rapid_tpu/messaging/_corpus.py", "check_wire_schema"),
    "field_number_drift.py": ("rapid_tpu/interop/_corpus.py", "check_wire_schema"),
    "clean_wire_schema.py": ("rapid_tpu/messaging/_corpus.py", "check_wire_schema"),
    "unreachable_dispatch_arm.py": ("rapid_tpu/protocol/_corpus.py", "check_dispatch"),
    "shadowed_arm.py": ("rapid_tpu/protocol/_corpus.py", "check_dispatch"),
    "clean_dispatch.py": ("rapid_tpu/protocol/_corpus.py", "check_dispatch"),
    "leaked_task.py": ("rapid_tpu/messaging/_corpus.py", "check_taskflow"),
    "swallowed_exception.py": ("rapid_tpu/messaging/_corpus.py", "check_taskflow"),
    "cancellation_swallow.py": ("rapid_tpu/messaging/_corpus.py", "check_taskflow"),
    "unawaited_coroutine.py": ("rapid_tpu/messaging/_corpus.py", "check_taskflow"),
    "clean_taskflow.py": ("rapid_tpu/messaging/_corpus.py", "check_taskflow"),
    "unseeded_random.py": ("rapid_tpu/messaging/_corpus.py", "check_determinism"),
    "clean_determinism.py": ("rapid_tpu/messaging/_corpus.py", "check_determinism"),
    # ISSUE 15: retry-backoff jitter in the serving supervision tier must
    # stay seeded (a fault drill replays bit-identically) — the defect +
    # clean pair live at the serving prefix the discipline now covers.
    "unseeded_backoff.py": ("rapid_tpu/serving/_corpus.py", "check_determinism"),
    "clean_backoff.py": ("rapid_tpu/serving/_corpus.py", "check_determinism"),
    "ledger_event_name.py": ("rapid_tpu/models/_corpus.py", "check_ledger"),
    "clean_ledger.py": ("rapid_tpu/models/_corpus.py", "check_ledger"),
    # device_program corpus files COMPILE their miniature programs (on the
    # session's 8-device CPU mesh) and compare against the inline HLO_LOCK
    # each carries — the compiled-artifact twin of the AST corpus.
    "hot_loop_collective.py": ("rapid_tpu/models/_corpus.py", "check_device_program"),
    "donation_dropped.py": ("rapid_tpu/models/_corpus.py", "check_device_program"),
    "clean_device_program.py": ("rapid_tpu/models/_corpus.py", "check_device_program"),
    "host_sync_in_hot_path.py": ("rapid_tpu/ops/_corpus.py", "check_sharding"),
    "host_sync_in_stream.py": ("rapid_tpu/serving/_corpus.py", "check_sharding"),
    "missing_partition_spec.py": ("rapid_tpu/parallel/_corpus.py", "check_sharding"),
    "missing_partition_rule.py": ("rapid_tpu/parallel/_corpus.py", "check_sharding"),
    "tenant_partition_rule.py": ("rapid_tpu/tenancy/_corpus.py", "check_sharding"),
    "retrace_hazard.py": ("rapid_tpu/models/_corpus.py", "check_sharding"),
    "dtype_widening.py": ("rapid_tpu/models/_corpus.py", "check_sharding"),
    "clean_dtype_widening.py": ("rapid_tpu/models/_corpus.py", "check_sharding"),
    "clean_sharding.py": ("rapid_tpu/parallel/_corpus.py", "check_sharding"),
    "chaos_unknown_kind.py": ("rapid_tpu/sim/_corpus.py", "check_chaosvocab"),
    "clean_chaosvocab.py": ("rapid_tpu/sim/_corpus.py", "check_chaosvocab"),
    "telemetry_unmarked_fetch.py": ("rapid_tpu/tenancy/_corpus.py", "check_telemetry"),
    "clean_telemetry.py": ("rapid_tpu/tenancy/_corpus.py", "check_telemetry"),
    # ISSUE 17: the round-trace ring rides the telemetry fetch discipline —
    # unmarked ring decodes (digest jits or direct spellings over
    # ``trace_ring`` / ``tr_*``) block like unmarked lane fetches, while
    # the decoded host-side summaries stay free.
    "trace_unmarked_fetch.py": ("rapid_tpu/serving/_corpus.py", "check_telemetry"),
    "clean_trace_fetch.py": ("rapid_tpu/serving/_corpus.py", "check_telemetry"),
    # ISSUE 19: the dataflow corpus TRACES its miniature programs (no
    # compile) and runs the jaxpr provenance proofs over each — observer
    # feedback and a cross-tenant gather against the silent clean twin.
    "dataflow_observer_leak.py": ("rapid_tpu/models/_corpus.py", "check_dataflow"),
    "clean_dataflow.py": ("rapid_tpu/models/_corpus.py", "check_dataflow"),
}


def _expected_markers(path: Path):
    return sorted(
        (m.group(1), lineno)
        for lineno, line in enumerate(path.read_text().splitlines(), 1)
        if (m := _EXPECT_RE.search(line))
    )


def test_corpus_is_complete():
    # Every corpus file is consumed by exactly one parametrized case below
    # (a stray file would silently be a no-op fixture).
    on_disk = {p.name for p in CORPUS.glob("*.py")}
    assert on_disk == set(_CORPUS_CHECKERS) | {"syntax_error.py"}


@pytest.mark.parametrize("name", sorted(_CORPUS_CHECKERS))
def test_lint_corpus(name):
    pretend_rel, checker_name = _CORPUS_CHECKERS[name]
    checker = getattr(staticcheck, checker_name)
    source = (CORPUS / name).read_text()
    findings = checker(staticcheck.core.REPO / pretend_rel, source=source)
    got = sorted((f.check, f.lineno) for f in findings)
    assert got == _expected_markers(CORPUS / name), "\n".join(
        str(f) for f in findings
    )


def test_lint_corpus_syntax_error():
    # Fed through the real driver (an explicit file root bypasses the
    # corpus exclusion): the parse failure becomes the file's one finding.
    findings = staticcheck.run([str(CORPUS / "syntax_error.py")])
    got = sorted((f.check, f.lineno) for f in findings)
    assert got == _expected_markers(CORPUS / "syntax_error.py")


def test_corpus_is_excluded_from_tree_sweeps():
    # The corpus exists to be defective; directory walks must skip it or
    # the whole-tree gate fails on purpose-built defects.
    swept = {str(p) for p in staticcheck.iter_files(("tests",))}
    assert not any("lint_corpus" in p for p in swept)


# ---------------------------------------------------------------------------
# Concurrency analyzer unit behaviors not covered by the corpus
# ---------------------------------------------------------------------------


def _concurrency(source: str, rel: str = "rapid_tpu/protocol/_probe.py"):
    return staticcheck.check_concurrency(
        staticcheck.core.REPO / rel, source=textwrap.dedent(source)
    )


def test_concurrency_checks_gate_on_package_prefix():
    src = """
    import asyncio

    class C:
        def __init__(self):
            self._lock = asyncio.Lock()
            self._x = 0  # guarded-by: _lock

        async def poke(self):
            self._x += 1
    """
    assert [f.check for f in _concurrency(src)] == ["unguarded-mutation"]
    assert _concurrency(src, rel="rapid_tpu/utils/_probe.py") == []


def test_guarded_by_annotation_typo_is_flagged():
    # A typo'd lock name must fail the gate, not silently guard nothing.
    src = """
    import asyncio

    class C:
        def __init__(self):
            self._lock = asyncio.Lock()
            self._x = 0  # guarded-by: _lokc
    """
    findings = _concurrency(src)
    assert [f.check for f in findings] == ["guarded-by-annotation"]
    assert "_lokc" in findings[0].message


def test_unguarded_ok_comment_allowlists_a_mutation():
    src = """
    import asyncio

    class C:
        def __init__(self):
            self._lock = asyncio.Lock()
            self._x = 0  # guarded-by: _lock

        async def poke(self):
            self._x += 1  # unguarded-ok: single-writer during bootstrap
    """
    assert _concurrency(src) == []


def test_escaped_and_unknown_contexts_are_skipped():
    # Methods registered as callbacks (or never called intra-class) have
    # unknowable lock contexts: mutations there must not convict.
    src = """
    import asyncio

    class C:
        def __init__(self, bus):
            self._lock = asyncio.Lock()
            self._x = 0  # guarded-by: _lock
            bus.subscribe(self._on_event)

        def _on_event(self, _evt):
            self._x += 1  # callback: context unknown, skip

        def _never_called_here(self):
            self._x += 1  # no intra-class call site: skip
    """
    assert _concurrency(src) == []


# ---------------------------------------------------------------------------
# Clock-injection extensions (time_ns, datetime spellings, monitoring/)
# ---------------------------------------------------------------------------


def test_clock_check_covers_new_spellings_and_monitoring():
    src = textwrap.dedent(
        """
        import time
        import datetime

        def stamp():
            return (
                time.time_ns(),
                datetime.datetime.now(),
            )
        """
    )
    for rel in ("rapid_tpu/protocol/_probe.py", "rapid_tpu/monitoring/_probe.py"):
        findings = staticcheck.check_clock_injection(
            staticcheck.core.REPO / rel, source=src
        )
        assert [f.check for f in findings] == ["clock-injection"] * 2, findings
    outside = staticcheck.check_clock_injection(
        staticcheck.core.REPO / "rapid_tpu" / "utils" / "_probe.py", source=src
    )
    assert outside == []


def test_wall_clock_ok_comment_allowlists_a_read():
    src = textwrap.dedent(
        """
        import time

        def stamp():
            return time.time()  # wall-clock-ok: operator-facing log line
        """
    )
    findings = staticcheck.check_clock_injection(
        staticcheck.core.REPO / "rapid_tpu" / "monitoring" / "_probe.py", source=src
    )
    assert findings == []


# ---------------------------------------------------------------------------
# Wire-schema lockfile: round-trip, drift naming, end-to-end gate
# ---------------------------------------------------------------------------


def _wire_surface():
    import ast

    from analysis import wire_schema

    trees = [
        (ast.parse((staticcheck.core.REPO / rel).read_text()), rel)
        for rel in staticcheck.WIRE_FILES
    ]
    return wire_schema, wire_schema.extract_surface(trees)


def test_wire_lock_round_trips_clean():
    # The committed lock IS the live surface: regenerating changes nothing,
    # and both the cross-check and the lock comparison are silent.
    wire_schema, surface = _wire_surface()
    committed = json.loads((staticcheck.core.REPO / staticcheck.LOCK_REL).read_text())
    committed.pop("_comment", None)
    assert wire_schema.surface_to_lock(surface) == committed
    assert wire_schema.cross_check(surface) == []
    assert wire_schema.compare_lock(surface, committed) == []


def test_wire_lock_drift_names_the_drifted_message():
    # Buf-style breaking-change reports: each class of mutation (tag
    # renumber, proto field renumber, dataclass field reorder) produces a
    # wire-lock-drift finding naming the message type and the regen command.
    wire_schema, surface = _wire_surface()
    lock = wire_schema.surface_to_lock(surface)
    lock["request_tags"]["JoinMessage"] = 12
    lock["proto"]["Phase1bMessage"]["vval"] = 9
    lock["fields"]["JoinResponse"] = list(reversed(lock["fields"]["JoinResponse"]))
    findings = wire_schema.compare_lock(surface, lock)
    assert {f.check for f in findings} == {"wire-lock-drift"}
    messages = [f.message for f in findings]
    assert any("JoinMessage" in m and "12" in m for m in messages)
    assert any("Phase1bMessage" in m and "vval" in m for m in messages)
    assert any("JoinResponse" in m and "field order" in m for m in messages)
    assert all("--update-wire-lock" in m for m in messages)


def test_tampered_lock_fails_the_tree_gate(tmp_path, monkeypatch):
    # End-to-end through the tree-mode entry the driver calls: a lock that
    # disagrees with the live mirrors produces findings (exit 1 at the CLI).
    import ast

    from analysis import wire_schema

    lock = json.loads((staticcheck.core.REPO / staticcheck.LOCK_REL).read_text())
    lock["response_tags"]["ProbeResponse"] = 9
    del lock["request_tags"]["LeaveMessage"]
    tampered = tmp_path / "wire.lock.json"
    tampered.write_text(json.dumps(lock))
    monkeypatch.setattr(wire_schema, "LOCK_REL", str(tampered))
    trees = [
        (ast.parse((staticcheck.core.REPO / rel).read_text()), rel)
        for rel in staticcheck.WIRE_FILES
    ]
    findings = wire_schema.check_wire_lock(trees)
    assert findings and {f.check for f in findings} == {"wire-lock-drift"}
    assert any("ProbeResponse" in f.message for f in findings)
    assert any("LeaveMessage" in f.message for f in findings)


def test_narrowed_roots_still_run_intra_file_wire_checks():
    # A per-file CLI invocation gets the intra-file wire checks (tree
    # sweeps run the merged three-file check instead, so defects are never
    # double-reported). The corpus's seeded tag reuse, fed through the real
    # driver as an explicit root:
    findings = staticcheck.run([str(CORPUS / "tag_reuse.py")])
    assert [f.check for f in findings] == ["tag-reuse"]


def test_wire_check_is_presence_gated_per_file():
    # A real mirror file analyzed ALONE must not produce cross-file noise:
    # codec.py has tags+arms but no union, types.py has the union but no
    # tags — each is internally consistent, so each is silent. The merged
    # tree-mode check owns the cross-file obligations.
    for rel in staticcheck.WIRE_FILES:
        findings = staticcheck.check_wire_schema(staticcheck.core.REPO / rel)
        assert findings == [], (rel, findings)


# ---------------------------------------------------------------------------
# Dispatch analyzer unit behaviors not covered by the corpus
# ---------------------------------------------------------------------------


_MINI_DISPATCH_PRELUDE = """
from dataclasses import dataclass
from typing import Union


@dataclass(frozen=True)
class Ping:
    sender: str


@dataclass(frozen=True)
class Ack:
    pass


RapidRequest = Union[Ping]
RapidResponse = Union[Ack]
"""


def _dispatch(source: str, rel: str = "rapid_tpu/protocol/_probe.py"):
    return staticcheck.check_dispatch(
        staticcheck.core.REPO / rel, source=textwrap.dedent(source)
    )


def test_dispatch_return_type_resolved_through_helper_annotation():
    src = _MINI_DISPATCH_PRELUDE + """
class S:
    async def handle_message(self, request):
        if isinstance(request, Ping):
            return self._handle(request)
        raise TypeError(request)

    def _handle(self, request) -> Ping:
        return Ping("me")
"""
    findings = _dispatch(src)
    assert [f.check for f in findings] == ["dispatch-return"]
    assert "not a RapidResponse member" in findings[0].message


def test_dispatched_elsewhere_typo_is_flagged():
    # A stale or typo'd exemption must fail the gate, not silently excuse
    # a genuinely unreachable member.
    src = _MINI_DISPATCH_PRELUDE + """
class S:
    # dispatched-elsewhere: Gone
    async def handle_message(self, request):
        if isinstance(request, Ping):
            return Ack()
        raise TypeError(request)
"""
    findings = _dispatch(src)
    assert [f.check for f in findings] == ["unreachable-dispatch-arm"]
    assert "Gone" in findings[0].message and "stale or typo'd" in findings[0].message


def test_dispatch_gates_on_protocol_prefix():
    src = _MINI_DISPATCH_PRELUDE + """
class S:
    async def handle_message(self, request):
        raise TypeError(request)
"""
    assert _dispatch(src, rel="rapid_tpu/utils/_probe.py") == []
    assert [f.check for f in _dispatch(src)] == ["unreachable-dispatch-arm"]


# ---------------------------------------------------------------------------
# Taskflow analyzer unit behaviors not covered by the corpus
# ---------------------------------------------------------------------------


def _taskflow(source: str, rel: str = "rapid_tpu/utils/_probe.py"):
    return staticcheck.check_taskflow(
        staticcheck.core.REPO / rel, source=textwrap.dedent(source)
    )


def test_taskflow_gates_on_library_prefix():
    src = """
    import asyncio

    def fire(work):
        asyncio.ensure_future(work())
    """
    assert [f.check for f in _taskflow(src)] == ["leaked-task"]
    assert _taskflow(src, rel="tools/_probe.py") == []


def test_taskflow_ok_comment_allowlists_a_finding():
    src = """
    import asyncio

    def fire(work):
        asyncio.ensure_future(work())  # taskflow-ok: test shim, loop torn down next line
    """
    assert _taskflow(src) == []


def test_plain_except_exception_in_async_def_is_not_a_cancellation_swallow():
    # CancelledError derives from BaseException since 3.8: a broad-but-
    # justified Exception catch lets cancellation through and must not be
    # convicted; an unjustified BaseException catch is convicted twice
    # (it both swallows errors and absorbs cancellation).
    src = """
    import logging

    LOG = logging.getLogger(__name__)

    async def loop(tick):
        while True:
            try:
                await tick()
            except Exception:  # noqa: BLE001 — the loop must survive
                LOG.exception("tick failed")
    """
    assert _taskflow(src) == []
    src_base = """
    async def loop(tick):
        while True:
            try:
                await tick()
            except BaseException:
                pass
    """
    assert sorted(f.check for f in _taskflow(src_base)) == [
        "cancellation-swallow", "swallowed-exception",
    ]


# ---------------------------------------------------------------------------
# CLI contract: --json / --select / --ignore, human output + exit codes
# ---------------------------------------------------------------------------


def _run_cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = Path(staticcheck.__file__).resolve()
    return subprocess.run(
        [sys.executable, str(script), *args],
        capture_output=True, text=True, env=env, timeout=120,
    )


@pytest.mark.slow
def test_cli_json_select_ignore_and_exit_codes(tmp_path):
    # Rides the unfiltered check.sh pass (~15 s wall: each CLI invocation
    # is a fresh interpreter paying full import + analysis); the in-process
    # driver tests above pin the same select/ignore/exit semantics.
    bad = tmp_path / "bad.py"
    bad.write_text("def f():\n    return mesage\n")

    as_json = _run_cli("--json", str(bad))
    assert as_json.returncode == 1
    objs = [json.loads(line) for line in as_json.stdout.splitlines()]
    assert [(o["check"], o["lineno"]) for o in objs] == [("undefined-name", 2)]
    assert objs[0]["path"] == str(bad) and "mesage" in objs[0]["message"]

    human = _run_cli(str(bad))
    assert human.returncode == 1
    assert "[undefined-name]" in human.stdout
    assert human.stdout.strip().endswith("staticcheck: 1 finding(s)")

    ignored = _run_cli("--ignore", "undefined-name", str(bad))
    assert ignored.returncode == 0
    assert ignored.stdout.strip().endswith("staticcheck: 0 finding(s)")

    selected = _run_cli("--select", "clock-injection", "--json", str(bad))
    assert selected.returncode == 0 and selected.stdout.strip() == ""

    typo = _run_cli("--select", "no-such-check", str(bad))
    assert typo.returncode == 2 and "no-such-check" in typo.stderr


def test_cli_families_lists_all_families():
    assert len(staticcheck.FAMILIES) == 16
    result = _run_cli("--families")
    assert result.returncode == 0
    for name, _description in staticcheck.FAMILIES:
        assert name in result.stdout, name
    assert "cost_model" not in result.stdout
    helped = _run_cli("--help").stdout
    # the one lockfile: a wire format peers must agree on
    assert set(re.findall(r"--update-[a-z-]+", helped)) == {"--update-wire-lock"}


def test_cli_update_wire_lock_is_a_deterministic_round_trip(
    tmp_path, monkeypatch, capsys
):
    # Regenerating over an unchanged tree produces the byte-identical lock —
    # the committed file is exactly what the generator emits, so the gate
    # and the regen command can never fight each other. Regenerate into a
    # REDIRECTED path: writing the repo's lock in place would silently
    # overwrite the committed file with the live surface — masking the very
    # divergence this test exists to catch.
    from analysis import wire_schema

    committed = (staticcheck.core.REPO / staticcheck.LOCK_REL).read_text()
    target = tmp_path / "wire.lock.json"
    monkeypatch.setattr(wire_schema, "LOCK_REL", str(target))
    rc = staticcheck.main(["--update-wire-lock"])
    assert rc == 0
    assert "wrote" in capsys.readouterr().out
    assert target.read_text() == committed


# ---------------------------------------------------------------------------
# Sharding analyzer: *_argnames spellings must resolve, not false-positive
# ---------------------------------------------------------------------------


def _sharding(source: str, rel: str = "rapid_tpu/models/_probe.py"):
    return staticcheck.check_sharding(
        staticcheck.core.REPO / rel, source=textwrap.dedent(source)
    )


def test_donate_argnames_spelling_is_recognized_not_flagged():
    # donate_argnames=("state",) donates the pytree just as argnums would —
    # flagging it (and demanding a bogus # donate-ok:) violates
    # skip-don't-guess.
    findings = _sharding(
        """
        import jax

        def step_impl(cfg, state, faults):
            del cfg
            return state + faults

        step = jax.jit(step_impl, static_argnums=(0,),
                       donate_argnames=("state",))
        """
    )
    assert findings == [], findings


def test_static_argnames_pins_the_position_for_retrace_check():
    # jax maps static_argnames onto positions for positional calls, so a
    # bare literal there never retraces; an unpinned traced position next
    # to it must still flag.
    findings = _sharding(
        """
        import jax

        def run_impl(cfg, values, max_steps, rounds):
            del cfg
            return values * max_steps * rounds

        run = jax.jit(run_impl, static_argnums=(0,),
                      static_argnames=("max_steps",))

        def drive(cfg, values):
            ok = run(cfg, values, 96, jax.numpy.int32(4))
            bad = run(cfg, values, 96, 4)
            return ok, bad
        """
    )
    assert [f.check for f in findings] == ["retrace-hazard"], findings
    assert "position 3" in findings[0].message, findings[0].message
