"""tools/perfview.py: stage-timeline rendering of run ledgers, the perf
trajectory over bench-round JSON artifacts (with its trust flags — the
acceptance surface for "no blind perf points"), and the Chrome trace output.
"""

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import perfview  # noqa: E402  — tools/perfview.py

from rapid_tpu.utils.ledger import LedgerEvent, RunLedger  # noqa: E402


def _complete_ledger(tmp_path, fail_in=None):
    path = tmp_path / "run.jsonl"
    ledger = RunLedger(str(path), run_id="r1")
    ledger.emit(LedgerEvent.RUN_BEGIN, mode="inline", git_rev="abc1234",
                code_hash="deadbeefdeadbeef")
    for stage in ("devices_init", "state_build", "warmup_compile"):
        if stage == fail_in:
            try:
                with ledger.stage(stage, timeout_s=60):
                    raise RuntimeError("synthetic failure")
            except RuntimeError:
                pass
            ledger.emit(LedgerEvent.RUN_FAIL, error="RuntimeError",
                        last_completed_stage="state_build")
            ledger.close()
            return path
        with ledger.stage(stage, timeout_s=60, n=256):
            pass
    ledger.emit(LedgerEvent.COMPILE_STATS, stage="warmup_compile",
                compiles=4, compile_ms=4117.2)
    ledger.emit(LedgerEvent.RUN_END, outcome="completed")
    ledger.close()
    return path


def test_renders_complete_ledger_timeline(tmp_path, capsys):
    path = _complete_ledger(tmp_path)
    assert perfview.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "git_rev=abc1234" in out
    for stage in ("devices_init", "state_build", "warmup_compile"):
        assert stage in out
    assert "compile_stats" in out
    assert "outcome: completed" in out


def test_renders_failed_ledger_pointing_at_last_stage(tmp_path, capsys):
    path = _complete_ledger(tmp_path, fail_in="warmup_compile")
    assert perfview.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "FAIL" in out
    assert "last completed stage: state_build" in out


def test_wedged_ledger_shows_open_stage(tmp_path, capsys):
    path = tmp_path / "run.jsonl"
    ledger = RunLedger(str(path))
    with ledger.stage("devices_init"):
        pass
    ledger.emit(LedgerEvent.STAGE_BEGIN, stage="state_build", timeout_s=900)
    ledger.close()  # process dies here; no end event ever lands
    assert perfview.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "OPEN" in out
    assert "still running or killed mid-run (in 'state_build')" in out


def test_trajectory_renders_the_round_fixtures(capsys):
    """A trajectory of bench-round artifacts renders one row per round; the
    alert_deliveries_per_sec ≈ 4.96e10 class of derived-metric bug is
    visible at a glance on the point that carries it."""
    rounds = sorted(str(p) for p in (REPO / "tests" / "data" / "bench_rounds").glob("*.json"))
    assert len(rounds) == 2
    assert perfview.main(rounds) == 0
    out = capsys.readouterr().out
    lines = {line.split()[0]: line for line in out.splitlines()
             if line.startswith("BENCH_")}
    assert "suspect-rate" in lines["BENCH_x02"]
    assert "suspect-rate" not in lines["BENCH_x01"]
    assert "cpu" in lines["BENCH_x01"] and "tpu" in lines["BENCH_x02"]


def test_trajectory_accepts_bare_metric_json(tmp_path, capsys):
    point = tmp_path / "round.json"
    point.write_text(json.dumps({
        "metric": "churn_resolution_ms_n256_churn5pct", "value": 15.0,
        "unit": "ms", "vs_baseline": 33.3, "platform": "cpu",
        "alert_deliveries_per_sec": 511515.0,
    }))
    hole = tmp_path / "hole.json"
    hole.write_text(json.dumps({
        "metric": "churn_resolution_ms_n100000",
        "error": "accelerator_unavailable",
    }))
    assert perfview.main([str(point), str(hole)]) == 0
    out = capsys.readouterr().out
    row = next(line for line in out.splitlines() if line.startswith("round"))
    assert "live" in row and "suspect-rate" not in row
    assert "hole" in next(line for line in out.splitlines()
                          if line.startswith("hole"))


def test_trajectory_renders_headline_column_and_flags_missing(tmp_path, capsys):
    """ISSUE 9: the n1M_crash1pct_ms headline renders as its own trajectory
    column; an AUDITED round (carries hlo_audit) that omits both the value
    and its explicit n1M_status marker flags headline-missing; pre-audit
    historical rounds are exempt."""
    audit = {"sharded2d_wave": {"collectives": 5, "hot_loop_collectives": 1,
                                "temp_bytes": 10, "donation_dropped": 0}}
    points = {
        # Pre-audit historical round: exempt (sorts first).
        "BENCH_r20.json": {"metric": "m", "value": 1.0, "platform": "cpu"},
        # Audited + measured headline: value in the N1M column, no flag.
        "BENCH_r21.json": {"metric": "m", "value": 100.0, "platform": "tpu",
                           "hlo_audit": audit, "n1M_status": "live",
                           "n1M_crash1pct_ms": 709.2},
        # Audited + explicit ramped marker (CPU stage-path run): no flag.
        "BENCH_r22.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, "n1M_status": "ramped:4096",
                           "xl_point_ms": 40.0, "xl_n": 4096},
        # Audited round that silently dropped the headline: flagged.
        "BENCH_r23.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit},
    }
    paths = []
    for name, data in points.items():
        p = tmp_path / name
        p.write_text(json.dumps(data))
        paths.append(str(p))
    assert perfview.main(paths) == 0
    out = capsys.readouterr().out
    assert "N1M" in out.splitlines()[1]  # the trajectory header row
    lines = {line.split()[0]: line for line in out.splitlines()
             if line.startswith("BENCH_r2")}
    assert "709.2ms" in lines["BENCH_r21"]
    assert "headline-missing" not in lines["BENCH_r21"]
    assert "ramped:4096" in lines["BENCH_r22"]
    assert "headline-missing" not in lines["BENCH_r22"]
    assert "headline-missing" in lines["BENCH_r23"]
    assert "headline-missing" not in lines["BENCH_r20"]  # pre-audit history


def test_trajectory_renders_fleet_column_and_flags_missing(tmp_path, capsys):
    """ISSUE 10: tenant_view_changes_per_sec renders as its own trajectory
    column with the existing trust flags; an AUDITED round that omits both
    the value and its explicit tenant_fleet_status marker flags
    fleet-missing; pre-audit historical rounds are exempt."""
    audit = {"fleet3d_wave": {"collectives": 74, "hot_loop_collectives": 74,
                              "temp_bytes": 10, "donation_dropped": 0}}
    points = {
        # Pre-audit historical round: exempt (sorts first).
        "BENCH_r30.json": {"metric": "m", "value": 1.0, "platform": "cpu"},
        # Audited + measured fleet point: value in the FLEET column.
        "BENCH_r31.json": {"metric": "m", "value": 100.0, "platform": "tpu",
                           "hlo_audit": audit, "n1M_status": "live",
                           "tenant_fleet_status": "live",
                           "tenant_view_changes_per_sec": 5120.0,
                           "fleet_tenants": 256},
        # Audited + explicit ramped marker (CPU stage-path run): no flag.
        "BENCH_r32.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, "n1M_status": "ramped:256",
                           "tenant_fleet_status": "ramped:8x64"},
        # Audited round that silently dropped the fleet point: flagged.
        "BENCH_r33.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, "n1M_status": "ramped:256"},
    }
    paths = []
    for name, data in points.items():
        p = tmp_path / name
        p.write_text(json.dumps(data))
        paths.append(str(p))
    assert perfview.main(paths) == 0
    out = capsys.readouterr().out
    assert "FLEET" in out.splitlines()[1]  # the trajectory header row
    lines = {line.split()[0]: line for line in out.splitlines()
             if line.startswith("BENCH_r3")}
    assert "5120.0/s" in lines["BENCH_r31"]
    assert "fleet-missing" not in lines["BENCH_r31"]
    assert "ramped:8x64" in lines["BENCH_r32"]
    assert "fleet-missing" not in lines["BENCH_r32"]
    assert "fleet-missing" in lines["BENCH_r33"]
    assert "fleet-missing" not in lines["BENCH_r30"]  # pre-audit history


def test_trajectory_renders_stream_column_and_flags_missing(tmp_path, capsys):
    """ISSUE 11: stream_view_changes_per_sec renders as its own trajectory
    column (with the p99 alert->commit beside it) under the existing trust
    flags; an AUDITED round that omits both the value and its explicit
    stream_status marker flags stream-missing; pre-audit historical rounds
    are exempt."""
    audit = {"sharded2d_wave": {"collectives": 5, "hot_loop_collectives": 1,
                                "temp_bytes": 10, "donation_dropped": 0}}
    points = {
        # Pre-audit historical round: exempt (sorts first).
        "BENCH_r40.json": {"metric": "m", "value": 1.0, "platform": "cpu"},
        # Audited + measured stream point: rate + p99 in the STREAM column.
        "BENCH_r41.json": {"metric": "m", "value": 100.0, "platform": "tpu",
                           "hlo_audit": audit, "n1M_status": "live",
                           "tenant_fleet_status": "live",
                           "stream_status": "live",
                           "stream_view_changes_per_sec": 84.5,
                           "stream_p99_alert_to_commit_ms": 41.03,
                           "stream_overlap_efficiency": 0.91},
        # Audited + explicit ramped marker (CPU pipeline exercise): no flag.
        "BENCH_r42.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, "n1M_status": "ramped:256",
                           "tenant_fleet_status": "ramped:8x64",
                           "stream_status": "ramped:12x96"},
        # Audited round that silently dropped the stream point: flagged.
        "BENCH_r43.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, "n1M_status": "ramped:256",
                           "tenant_fleet_status": "ramped:8x64"},
    }
    paths = []
    for name, data in points.items():
        p = tmp_path / name
        p.write_text(json.dumps(data))
        paths.append(str(p))
    assert perfview.main(paths) == 0
    out = capsys.readouterr().out
    assert "STREAM" in out.splitlines()[1]  # the trajectory header row
    lines = {line.split()[0]: line for line in out.splitlines()
             if line.startswith("BENCH_r4")}
    assert "84.5/s" in lines["BENCH_r41"]
    assert "p99=41.0ms" in lines["BENCH_r41"]
    assert "stream-missing" not in lines["BENCH_r41"]
    assert "ramped:12x96" in lines["BENCH_r42"]
    assert "stream-missing" not in lines["BENCH_r42"]
    assert "stream-missing" in lines["BENCH_r43"]
    assert "stream-missing" not in lines["BENCH_r40"]  # pre-audit history


def test_trajectory_renders_chaos_column_and_flags_missing(tmp_path, capsys):
    """ISSUE 12: chaos_scenarios_per_sec renders as its own trajectory
    column (with the fleet tenant count beside it) under the existing
    trust flags; an AUDITED round that omits both the value and its
    explicit chaos_status marker flags chaos-missing; pre-audit historical
    rounds are exempt."""
    audit = {"fleet3d_wave": {"collectives": 74, "hot_loop_collectives": 74,
                              "temp_bytes": 10, "donation_dropped": 0}}
    points = {
        # Pre-audit historical round: exempt (sorts first).
        "BENCH_r50.json": {"metric": "m", "value": 1.0, "platform": "cpu"},
        # Audited + measured chaos point: rate + tenants in CHAOS column.
        "BENCH_r51.json": {"metric": "m", "value": 100.0, "platform": "tpu",
                           "hlo_audit": audit, "n1M_status": "live",
                           "tenant_fleet_status": "live",
                           "stream_status": "live",
                           "chaos_status": "live",
                           "chaos_scenarios_per_sec": 412.5,
                           "chaos_tenants": 256},
        # Audited + explicit ramped marker (CPU stage-path run): no flag.
        "BENCH_r52.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, "n1M_status": "ramped:256",
                           "tenant_fleet_status": "ramped:8x64",
                           "stream_status": "ramped:12x96",
                           "chaos_status": "ramped:12x12"},
        # Audited round that silently dropped the chaos point: flagged.
        "BENCH_r53.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, "n1M_status": "ramped:256",
                           "tenant_fleet_status": "ramped:8x64",
                           "stream_status": "ramped:12x96"},
    }
    paths = []
    for name, data in points.items():
        p = tmp_path / name
        p.write_text(json.dumps(data))
        paths.append(str(p))
    assert perfview.main(paths) == 0
    out = capsys.readouterr().out
    assert "CHAOS" in out.splitlines()[1]  # the trajectory header row
    lines = {line.split()[0]: line for line in out.splitlines()
             if line.startswith("BENCH_r5")}
    assert "412.5/s B=256" in lines["BENCH_r51"]
    assert "chaos-missing" not in lines["BENCH_r51"]
    assert "ramped:12x12" in lines["BENCH_r52"]
    assert "chaos-missing" not in lines["BENCH_r52"]
    assert "chaos-missing" in lines["BENCH_r53"]
    assert "chaos-missing" not in lines["BENCH_r50"]  # pre-audit history


def test_trajectory_renders_recovery_column_and_flags_missing(tmp_path, capsys):
    """ISSUE 15: recovery_mttr_ms renders as the RECOVERY trajectory
    column (with a DIVERGED callout when the resumed run failed its
    bit-identity check) under the existing trust flags; an AUDITED round
    that omits both the value and its explicit recovery_status marker
    flags recovery-missing; pre-audit historical rounds are exempt."""
    audit = {"step": {"collectives": 0, "hot_loop_collectives": 0,
                      "temp_bytes": 10, "donation_dropped": 0}}
    base = {"n1M_status": "ramped:256", "tenant_fleet_status": "ramped:8x64",
            "stream_status": "ramped:12x96", "chaos_status": "ramped:12x12",
            "mem_status": "computed:cpu"}
    points = {
        # Pre-audit historical round: exempt (sorts first).
        "BENCH_r60.json": {"metric": "m", "value": 1.0, "platform": "cpu"},
        # Audited + measured drill: the MTTR in the RECOVERY column.
        "BENCH_r61.json": {"metric": "m", "value": 100.0, "platform": "tpu",
                           "hlo_audit": audit, **base,
                           "recovery_status": "live",
                           "recovery_mttr_ms": 182.4,
                           "recovery_bit_identical": True},
        # A resume that DIVERGED is called out beside its MTTR.
        "BENCH_r62.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **base,
                           "recovery_status": "ramped:6x64",
                           "recovery_mttr_ms": 20.9,
                           "recovery_bit_identical": False},
        # Audited + explicit status marker only (skipped drill): no flag.
        "BENCH_r63.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **base,
                           "recovery_status": "skipped-budget"},
        # Audited round that silently dropped the drill: flagged.
        "BENCH_r64.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **base},
    }
    paths = []
    for name, data in points.items():
        p = tmp_path / name
        p.write_text(json.dumps(data))
        paths.append(str(p))
    assert perfview.main(paths) == 0
    out = capsys.readouterr().out
    assert "RECOVERY" in out.splitlines()[1]  # the trajectory header row
    lines = {line.split()[0]: line for line in out.splitlines()
             if line.startswith("BENCH_r6")}
    assert "182.4ms mttr" in lines["BENCH_r61"]
    assert "DIVERGED" not in lines["BENCH_r61"]
    assert "recovery-missing" not in lines["BENCH_r61"]
    assert "20.9ms mttr DIVERGED" in lines["BENCH_r62"]
    assert "skipped-budget" in lines["BENCH_r63"]
    assert "recovery-missing" not in lines["BENCH_r63"]
    assert "recovery-missing" in lines["BENCH_r64"]
    assert "recovery-missing" not in lines["BENCH_r60"]  # pre-audit history


def test_trajectory_renders_mem_column_and_flags_missing(tmp_path, capsys):
    """ISSUE 13: bytes_per_member renders as the MEM trajectory column
    (compact figure with the wide one beside it) under the existing trust
    flags; an AUDITED round omitting both the value and its explicit
    mem_status marker flags mem-missing; pre-audit historical rounds are
    exempt."""
    audit = {"step": {"collectives": 0, "hot_loop_collectives": 0,
                      "temp_bytes": 10, "donation_dropped": 0}}
    common = {"n1M_status": "ramped:256", "tenant_fleet_status": "ramped:4x48",
              "stream_status": "ramped:6x48", "chaos_status": "ramped:4x12"}
    points = {
        # Pre-audit historical round: exempt (sorts first).
        "BENCH_r60.json": {"metric": "m", "value": 1.0, "platform": "cpu"},
        # Audited + measured memory point: bytes/member in the MEM column.
        "BENCH_r61.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **common,
                           "mem_status": "live:hlo-audit",
                           "bytes_per_member": 246.4,
                           "bytes_per_member_wide": 445.0},
        # Audited + explicit computed marker: status cell, no flag.
        "BENCH_r62.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **common,
                           "mem_status": "computed:audit-lacks-step-memory"},
        # Audited round that silently dropped the memory point: flagged.
        "BENCH_r63.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **common},
    }
    paths = []
    for name, data in points.items():
        p = tmp_path / name
        p.write_text(json.dumps(data))
        paths.append(str(p))
    assert perfview.main(paths) == 0
    out = capsys.readouterr().out
    assert "MEM" in out.splitlines()[1]  # the trajectory header row
    lines = {line.split()[0]: line for line in out.splitlines()
             if line.startswith("BENCH_r6")}
    assert "246B/m (wide 445)" in lines["BENCH_r61"]
    assert "mem-missing" not in lines["BENCH_r61"]
    assert "computed:audit-lacks-step-memory" in lines["BENCH_r62"]
    assert "mem-missing" not in lines["BENCH_r62"]
    assert "mem-missing" in lines["BENCH_r63"]
    assert "mem-missing" not in lines["BENCH_r60"]  # pre-audit history


def test_trajectory_renders_activity_column_and_flags_missing(tmp_path, capsys):
    """ISSUE 16: the device-telemetry activity fraction renders as the
    ACTIVITY trajectory column (fast-path share beside it) under the
    existing trust flags; an AUDITED round that omits both the numeric
    ``stream_active_fraction`` and its explicit ``activity_status`` marker
    flags activity-missing; pre-audit historical rounds are exempt."""
    audit = {"step_telem": {"collectives": 0, "hot_loop_collectives": 0,
                            "temp_bytes": 10, "donation_dropped": 0}}
    base = {"n1M_status": "ramped:256", "tenant_fleet_status": "ramped:8x64",
            "stream_status": "ramped:12x96", "chaos_status": "ramped:12x12",
            "mem_status": "computed:cpu", "recovery_status": "skipped-budget"}
    points = {
        # Pre-audit historical round: exempt (sorts first).
        "BENCH_r70.json": {"metric": "m", "value": 1.0, "platform": "cpu"},
        # Audited + measured activity: fraction + fast share in the column.
        "BENCH_r71.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **base,
                           "activity_status": "measured",
                           "stream_active_fraction": 0.0417,
                           "stream_fast_path_share": 0.88},
        # Audited + explicit status marker only (stream stage skipped, so
        # the lanes never ran): status cell, no flag.
        "BENCH_r72.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **base,
                           "activity_status": "skipped-budget"},
        # Audited round that silently dropped the activity point: flagged.
        "BENCH_r73.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **base},
    }
    paths = []
    for name, data in points.items():
        p = tmp_path / name
        p.write_text(json.dumps(data))
        paths.append(str(p))
    assert perfview.main(paths) == 0
    out = capsys.readouterr().out
    assert "ACTIVITY" in out.splitlines()[1]  # the trajectory header row
    lines = {line.split()[0]: line for line in out.splitlines()
             if line.startswith("BENCH_r7")}
    assert "4.2% fast=88%" in lines["BENCH_r71"]
    assert "activity-missing" not in lines["BENCH_r71"]
    assert "skipped-budget" in lines["BENCH_r72"]
    assert "activity-missing" not in lines["BENCH_r72"]
    assert "activity-missing" in lines["BENCH_r73"]
    assert "activity-missing" not in lines["BENCH_r70"]  # pre-audit history


def test_trajectory_renders_trace_column_and_flags_missing(tmp_path, capsys):
    """ISSUE 17: the round-trace ring's stream decomposition renders as the
    TRACE trajectory column (rounds-to-decision p99, worst wave beside it)
    under the same trust discipline as ACTIVITY: an AUDITED round that
    omits both the numeric ``round_trajectory.rounds_to_decision_p99`` and
    its explicit ``trace_status`` marker flags trace-missing; pre-audit
    historical rounds are exempt."""
    audit = {"step_trace": {"collectives": 0, "hot_loop_collectives": 0,
                            "temp_bytes": 10, "donation_dropped": 0}}
    base = {"n1M_status": "ramped:256", "tenant_fleet_status": "ramped:8x64",
            "stream_status": "ramped:12x96", "chaos_status": "ramped:12x12",
            "mem_status": "computed:cpu", "recovery_status": "skipped-budget",
            "activity_status": "skipped-budget"}
    points = {
        # Pre-audit historical round: exempt (sorts first).
        "BENCH_r80.json": {"metric": "m", "value": 1.0, "platform": "cpu"},
        # Audited + a measured trajectory: p99 + worst wave in the column.
        "BENCH_r81.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **base,
                           "trace_status": "measured",
                           "round_trajectory": {
                               "rounds_to_decision_p99": 3.0,
                               "rounds_to_decision_max": 4,
                           }},
        # Audited + explicit status marker only (trace=0 bench): status
        # cell, no flag.
        "BENCH_r82.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **base,
                           "trace_status": "skipped-budget"},
        # Audited round that silently dropped the trajectory: flagged.
        "BENCH_r83.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit, **base},
    }
    paths = []
    for name, data in points.items():
        p = tmp_path / name
        p.write_text(json.dumps(data))
        paths.append(str(p))
    assert perfview.main(paths) == 0
    out = capsys.readouterr().out
    assert "TRACE" in out.splitlines()[1]  # the trajectory header row
    lines = {line.split()[0]: line for line in out.splitlines()
             if line.startswith("BENCH_r8")}
    assert "p99=3.0r max=4" in lines["BENCH_r81"]
    assert "trace-missing" not in lines["BENCH_r81"]
    assert "skipped-budget" in lines["BENCH_r82"]
    assert "trace-missing" not in lines["BENCH_r82"]
    assert "trace-missing" in lines["BENCH_r83"]
    assert "trace-missing" not in lines["BENCH_r80"]  # pre-audit history


def test_chrome_trace_envelope(tmp_path, capsys):
    path = _complete_ledger(tmp_path)
    chrome_path = tmp_path / "trace.json"
    assert perfview.main([str(path), "--chrome", str(chrome_path)]) == 0
    with open(chrome_path) as f:
        chrome = json.load(f)
    # Same envelope traceview emits (Perfetto/chrome://tracing load it).
    assert set(chrome) == {"traceEvents", "displayTimeUnit"}
    assert chrome["displayTimeUnit"] == "ms"
    stages = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in stages} == {
        "devices_init", "state_build", "warmup_compile",
    }
    for event in stages:
        assert event["dur"] >= 0 and isinstance(event["ts"], (int, float))
    instants = [e for e in chrome["traceEvents"] if e["ph"] == "i"]
    assert any(e["name"] == "compile_stats" for e in instants)


def test_multi_run_ledger_renders_one_section_per_run(tmp_path, capsys):
    # The default bench_ledger.jsonl accumulates runs across invocations;
    # each run must render as its own timeline with its own outcome, never
    # one merged timeline under the first run's provenance.
    path = tmp_path / "run.jsonl"
    first = RunLedger(str(path), run_id="run-one")
    first.emit(LedgerEvent.RUN_BEGIN, mode="inline", git_rev="aaa1111")
    with first.stage("devices_init"):
        pass
    first.emit(LedgerEvent.RUN_END, outcome="completed")
    first.close()
    second = RunLedger(str(path), run_id="run-two")
    second.emit(LedgerEvent.RUN_BEGIN, mode="inline", git_rev="bbb2222")
    second.emit(LedgerEvent.RUN_FAIL, outcome="wedged",
                last_completed_stage=None)
    second.close()
    assert perfview.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "[run-one]" in out and "[run-two]" in out
    one, two = out.split("[run-two]")
    assert "outcome: completed" in one and "FAILED" not in one
    assert "outcome: FAILED (wedged)" in two
    runs = perfview.split_runs(perfview.read_ledger(str(path))[0])
    assert [run_id for run_id, _ in runs] == ["run-one", "run-two"]


def test_errors_cleanly_on_bad_inputs(tmp_path, capsys):
    missing = tmp_path / "missing.jsonl"
    assert perfview.main([str(missing)]) == 2
    assert "perfview:" in capsys.readouterr().err
    scalar = tmp_path / "scalar.json"
    scalar.write_text("42")
    assert perfview.main([str(scalar)]) == 2
    assert "not a bench metric artifact" in capsys.readouterr().err
    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    assert perfview.main([str(bad)]) == 2
    assert "invalid JSON" in capsys.readouterr().err


def test_trajectory_flags_collective_count_drift(tmp_path, capsys):
    # Rounds carrying bench.py's hlo_audit table are diffed pairwise: a
    # collective-count change between audited rounds flags the LATER point
    # hlo-drift; un-audited (or errored) rounds in between neither flag
    # nor reset the comparison baseline.
    def audit(hot):
        return {"sharded_wave": {"collectives": 10 + hot,
                                 "hot_loop_collectives": hot,
                                 "temp_bytes": 1000, "donation_dropped": 0}}

    points = {
        "BENCH_r11.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit(hot=2)},
        "BENCH_r12.json": {"metric": "m", "value": 1.0, "platform": "cpu"},
        "BENCH_r13.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": {"error": "no devices"}},
        "BENCH_r14.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit(hot=3)},
        "BENCH_r15.json": {"metric": "m", "value": 1.0, "platform": "cpu",
                           "hlo_audit": audit(hot=3)},
    }
    paths = []
    for name, data in points.items():
        p = tmp_path / name
        p.write_text(json.dumps(data))
        paths.append(str(p))
    assert perfview.main(paths) == 0
    out = capsys.readouterr().out
    lines = {line.split()[0]: line for line in out.splitlines()
             if line.startswith("BENCH_r1")}
    assert "hlo-drift" not in lines["BENCH_r11"]  # nothing earlier to diff
    assert "live" in lines["BENCH_r12"]  # un-audited round: no flag
    assert "live" in lines["BENCH_r13"]  # errored audit: no flag
    assert "hlo-drift" in lines["BENCH_r14"]  # 2 -> 3 vs r11's baseline
    assert "hlo-drift" not in lines["BENCH_r15"]  # stable vs r14
