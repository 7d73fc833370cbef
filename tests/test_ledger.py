"""Run-ledger semantics: registered vocabularies enforced at write time,
stage bracketing with durations and failure events, provenance stamping,
and crash-tolerant reading (torn final lines).
"""

import json

import pytest

from rapid_tpu.utils.ledger import (
    STAGE_NAMES,
    LedgerEvent,
    RunLedger,
    code_hash,
    last_completed_stage,
    open_stage,
    provenance,
    read_ledger,
)


def _events(path):
    events, skipped = read_ledger(str(path))
    return events


def test_emit_writes_validated_flushed_lines(tmp_path):
    path = tmp_path / "run.jsonl"
    ledger = RunLedger(str(path), run_id="r1")
    ledger.emit(LedgerEvent.RUN_BEGIN, mode="test")
    ledger.emit(LedgerEvent.RUN_END, outcome="completed")
    # Flushed per line: readable without closing the writer.
    events = _events(path)
    assert [e["event"] for e in events] == ["run_begin", "run_end"]
    assert all(e["run_id"] == "r1" for e in events)
    assert [e["seq"] for e in events] == [0, 1]
    assert all("t_s" in e and "wall" in e and "pid" in e for e in events)
    ledger.close()


def test_emit_rejects_unregistered_vocabulary(tmp_path):
    ledger = RunLedger(str(tmp_path / "run.jsonl"))
    with pytest.raises(TypeError, match="LedgerEvent members"):
        ledger.emit("run_begin")
    with pytest.raises(ValueError, match="unregistered ledger stage"):
        ledger.emit(LedgerEvent.STAGE_BEGIN, stage="made_up_stage")
    assert _events(tmp_path / "run.jsonl") == []  # nothing leaked
    ledger.close()


def test_stage_brackets_success_with_duration(tmp_path):
    path = tmp_path / "run.jsonl"
    ledger = RunLedger(str(path))
    with ledger.stage("state_build", timeout_s=60, n=1024):
        pass
    begin, end = _events(path)
    assert begin["event"] == "stage_begin" and begin["stage"] == "state_build"
    assert begin["timeout_s"] == 60 and begin["n"] == 1024
    assert end["event"] == "stage_end" and end["duration_ms"] >= 0
    assert last_completed_stage(_events(path)) == "state_build"
    assert open_stage(_events(path)) is None
    ledger.close()


def test_stage_failure_emits_stage_fail_and_reraises(tmp_path):
    path = tmp_path / "run.jsonl"
    ledger = RunLedger(str(path))
    with pytest.raises(RuntimeError, match="boom"):
        with ledger.stage("warmup_compile"):
            raise RuntimeError("boom")
    begin, fail = _events(path)
    assert fail["event"] == "stage_fail" and "boom" in fail["error"]
    # A failed stage is not a completed one...
    assert last_completed_stage(_events(path)) is None
    # ...but it is CLOSED: the run is not "stuck in" it.
    assert open_stage(_events(path)) is None
    ledger.close()


def test_open_stage_identifies_the_wedge_point(tmp_path):
    path = tmp_path / "run.jsonl"
    ledger = RunLedger(str(path))
    with ledger.stage("state_build"):
        pass
    ledger.emit(LedgerEvent.STAGE_BEGIN, stage="warmup_compile", timeout_s=900)
    # (process wedges here: no end ever arrives)
    stuck = open_stage(_events(path))
    assert stuck is not None and stuck["stage"] == "warmup_compile"
    assert stuck["timeout_s"] == 900
    assert last_completed_stage(_events(path)) == "state_build"
    ledger.close()


def test_read_ledger_tolerates_torn_and_foreign_lines(tmp_path):
    path = tmp_path / "run.jsonl"
    ledger = RunLedger(str(path))
    ledger.emit(LedgerEvent.RUN_BEGIN)
    ledger.close()
    with open(path, "a") as f:
        f.write('["not", "a", "ledger", "record"]\n')
        f.write('{"event": "stage_begin", "stage": "state_bui')  # torn write
    events, skipped = read_ledger(str(path))
    assert [e["event"] for e in events] == ["run_begin"]
    assert skipped == 2
    # A missing file reads as empty, never raises (a reader may poll the
    # ledger before the writer has written anything).
    assert read_ledger(str(tmp_path / "nope.jsonl")) == ([], 0)


def test_shared_t0_puts_processes_on_one_timeline(tmp_path):
    # A run may span several processes; passing the first writer's epoch
    # keeps every t_s on one timeline instead of restarting at 0 per process.
    import time

    path = tmp_path / "run.jsonl"
    parent = RunLedger(str(path), run_id="shared")
    child = RunLedger(str(path), run_id="shared", t0=parent.t0)
    assert child.t0 == parent.t0
    later = RunLedger(str(path), run_id="shared", t0=time.monotonic() - 100.0)
    later.emit(LedgerEvent.RUN_BEGIN)
    [event] = _events(path)
    assert event["t_s"] >= 100.0  # relative to the injected epoch
    parent.close()
    child.close()
    later.close()


def test_two_writers_share_one_file(tmp_path):
    # Two writers append to the same ledger; the merged stream stays
    # line-parseable and correlated by run_id.
    path = tmp_path / "run.jsonl"
    parent = RunLedger(str(path), run_id="shared")
    child = RunLedger(str(path), run_id="shared")
    parent.emit(LedgerEvent.RUN_BEGIN)
    with child.stage("devices_init"):
        parent.emit(LedgerEvent.COMPILE_STATS, compiles=1)
    parent.emit(LedgerEvent.RUN_END, outcome="live")
    events, skipped = read_ledger(str(path))
    assert skipped == 0 and len(events) == 5
    assert {e["run_id"] for e in events} == {"shared"}
    parent.close()
    child.close()


def test_provenance_stamps_git_rev_and_code_hash(tmp_path):
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "a.py").write_text("x = 1\n")
    (tmp_path / "main.py").write_text("print('hi')\n")
    stamp = provenance(str(tmp_path), ("main.py", "pkg"))
    assert set(stamp) == {"git_rev", "code_hash", "hash_roots"}
    assert stamp["hash_roots"] == ["main.py", "pkg"]
    # Not a git repo: rev is None, hash still present.
    assert stamp["git_rev"] is None
    assert len(stamp["code_hash"]) == 16


def test_code_hash_tracks_content_not_noise(tmp_path):
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "a.py").write_text("x = 1\n")
    before = code_hash(str(tmp_path), ("pkg",))
    assert code_hash(str(tmp_path), ("pkg",)) == before  # deterministic
    # Caches and bytecode never stale a hash...
    (tmp_path / "pkg" / "__pycache__").mkdir()
    (tmp_path / "pkg" / "__pycache__" / "a.pyc").write_text("junk")
    assert code_hash(str(tmp_path), ("pkg",)) == before
    # ...a real source edit always does.
    (tmp_path / "pkg" / "a.py").write_text("x = 2\n")
    assert code_hash(str(tmp_path), ("pkg",)) != before


def test_every_stage_name_is_json_safe_and_lowercase():
    for name in STAGE_NAMES:
        assert name == name.lower() and " " not in name
        json.dumps({"stage": name})
    for event in LedgerEvent:
        assert event.value == event.value.lower()
