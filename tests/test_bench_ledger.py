"""bench.py end-to-end through the run ledger: a CPU run emits a COMPLETE
JSONL ledger (every stage bracketed, provenance stamped, metric + run_end
recorded), and a run that finds no TPU and was not explicitly asked for the
CPU smoke fails LOUDLY — nonzero exit, the ledger pointing at the stage it
stopped in.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import bench
from rapid_tpu.utils.ledger import open_stage, read_ledger

REPO = Path(__file__).resolve().parent.parent
BENCH = str(REPO / "bench.py")


def _run_bench(tmp_path, *args, env_overrides=None, drop=(), timeout=240):
    env = dict(os.environ)
    for name in list(env):
        if name.startswith("RAPID_TPU_BENCH"):
            del env[name]
    for name in drop:
        env.pop(name, None)
    env["RAPID_TPU_BENCH_LEDGER"] = str(tmp_path / "ledger.jsonl")
    env.update(env_overrides or {})
    proc = subprocess.run(
        [sys.executable, BENCH, *args],
        capture_output=True, text=True, env=env, timeout=timeout,
        cwd=str(tmp_path),
    )
    events, skipped = read_ledger(str(tmp_path / "ledger.jsonl"))
    assert skipped == 0, f"unparseable ledger lines: {skipped}"
    return proc, events


def _stage_pairs(events):
    """{stage: [(begin, close)]} where close is the matching end/fail."""
    pairs = {}
    for record in events:
        kind = record.get("event")
        if kind == "stage_begin":
            pairs.setdefault(record["stage"], []).append([record, None])
        elif kind in ("stage_end", "stage_fail"):
            spans = pairs.get(record["stage"], [])
            open_spans = [s for s in spans if s[1] is None]
            assert open_spans, f"{kind} without begin: {record}"
            open_spans[-1][1] = record
    return pairs


def test_cpu_run_emits_complete_ledger(tmp_path):
    """The acceptance criterion: an explicit CPU bench run leaves a complete
    ledger — every stage begin+end, provenance stamped, derived metrics
    plausible — and its JSON line agrees with the ledger's metric event.
    One subprocess run also pins the ISSUE-9 headline path: the xl_point
    stage runs ramped-down on CPU (explicit marker, device-memory event
    alongside) and the opt-in stretch point runs in its own registered
    stage."""
    proc, events = _run_bench(
        tmp_path,
        env_overrides={
            "JAX_PLATFORMS": "cpu",
            "RAPID_TPU_BENCH_N": "256",
            # Tiny headline + stretch points: the FULL stage path runs
            # (ramped) without hardware-scale minutes. The stretch N equals
            # the headline N so the stretch stage reuses the compiled
            # executable (the stage path is what's under test, not a second
            # compile); the loss variant is dropped to keep this e2e's wall
            # clock near the pre-headline budget.
            "RAPID_TPU_BENCH_XL_N": "256",
            "RAPID_TPU_BENCH_STRETCH": "256",
            "RAPID_TPU_BENCH_XL_BUDGET_S": "100000",
            "RAPID_TPU_BENCH_NO_LOSS": "1",
            # Tiny tenant fleet: the FULL stage path runs (ramped) — one
            # warm-up + one timed lockstep wave over 4 mixed-scenario
            # tenants.
            "RAPID_TPU_BENCH_FLEET_B": "4",
            "RAPID_TPU_BENCH_FLEET_N": "48",
            # Tiny stream: the FULL pipelined path runs (ramped) — Poisson
            # churn double-buffered through both the single-cluster and
            # fleet stream drivers.
            "RAPID_TPU_BENCH_STREAM_WAVES": "6",
            "RAPID_TPU_BENCH_STREAM_N": "48",
            # Tiny adversarial-chaos fleet: the FULL stage path runs
            # (ramped) — warm-up + timed fuzz round over 4 mixed hostile
            # scenarios, oracle-checked clean.
            "RAPID_TPU_BENCH_CHAOS_B": "4",
            # Tiny self-healing drill: the FULL recovery path runs
            # (ramped) — injected transient failure, simulated kill,
            # checkpoint resume, bit-identity check.
            "RAPID_TPU_BENCH_RECOVERY_N": "48",
            "RAPID_TPU_BENCH_RECOVERY_WAVES": "4",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    [metric_line] = [l for l in proc.stdout.splitlines()
                     if l.startswith("{") and '"metric"' in l]
    result = json.loads(metric_line)
    assert result["platform"] == "cpu" and result["n_members"] == 256

    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_begin" and kinds[-1] == "run_end"
    begin = events[0]
    # Provenance: attributable to the exact source that produced it.
    assert begin["git_rev"] and begin["code_hash"]
    assert begin["hash_roots"] == ["bench.py", "rapid_tpu", "native"]
    # Every stage is bracketed: begin + end (or an explicit failure).
    pairs = _stage_pairs(events)
    for stage, spans in pairs.items():
        for span_begin, close in spans:
            assert close is not None, f"stage {stage} never closed"
            assert close["event"] == "stage_end"
            assert close["duration_ms"] >= 0
            assert span_begin.get("timeout_s", 0) > 0
    assert {"devices_init", "native_build", "state_build", "warmup_compile",
            "timed_samples", "rtt_probe"} <= set(pairs)
    assert open_stage(events) is None
    # Engine-tier events made it into the ledger.
    assert "compile_stats" in kinds and "device_memory" in kinds
    # The emitted JSON is also a ledger event (the trajectory's source of
    # truth survives even if stdout is lost).
    [metric_event] = [e for e in events if e["event"] == "metric"]
    assert metric_event["value"] == result["value"]
    # Derived metrics at the engine's cohort grain (the 4.96e10 bug class).
    assert abs(
        result["alert_deliveries_per_sec"]
        - result["alerts_per_sec"] * result["cohorts"]
    ) <= result["cohorts"]
    assert result["alert_deliveries_per_sec"] < 1e9
    assert result["compiles"] >= 1
    # ISSUE 9 headline path, same run: a ramped marker — never a fake 1M
    # number — with the measurement on the clearly-labeled xl_point_ms/xl_n
    # pair and device memory beside it; the stretch point is generic below
    # the named 10M goal.
    assert result["n1M_status"] == "ramped:256"
    assert "n1M_crash1pct_ms" not in result
    assert result["xl_n"] == 256 and result["xl_point_ms"] > 0
    assert "live_buffers" in result["xl_device_memory"]
    assert result["stretch_n"] == 256 and result["stretch_ms"] > 0
    assert "n10M_crash1pct_ms" not in result
    for stage in ("xl_point", "stretch_point"):
        [(span_begin, close)] = pairs[stage]
        assert close["event"] == "stage_end"
        assert span_begin["timeout_s"] > 0  # budget stamped for the reader
        assert span_begin["n"] == 256  # each point stage records its own N
    assert any(
        e["event"] == "device_memory" and e.get("stage") == "xl_point"
        for e in events
    )
    # ISSUE 10 fleet path, same run: the tenant_fleet stage ran ramped-down
    # in its own bracketed, budgeted stage with an explicit status marker —
    # the fleet metric is never silently absent.
    assert result["tenant_fleet_status"] == "ramped:4x48"
    assert result["fleet_tenants"] == 4
    assert result["fleet_view_changes"] >= 4  # every tenant cut at least once
    assert result["tenant_view_changes_per_sec"] > 0
    assert "live_buffers" in result["fleet_device_memory"]
    [(fleet_begin, fleet_close)] = pairs["tenant_fleet"]
    assert fleet_close["event"] == "stage_end"
    assert fleet_begin["timeout_s"] > 0
    assert fleet_begin["n"] == 4 * 48  # total fleet slots under test
    assert any(
        e["event"] == "device_memory" and e.get("stage") == "tenant_fleet"
        for e in events
    )
    # ISSUE 11 streaming path, same run: the stream stage drove Poisson
    # churn through the pipelined dispatch path (both serving shapes) in
    # its own bracketed, budgeted stage — sustained view-changes/sec, p99
    # alert->commit, and the overlap-efficiency ratio all land in the
    # emitted JSON with an explicit status marker (never silently absent).
    assert result["stream_status"] == "ramped:6x48"
    assert result["stream_waves"] == 6 and result["stream_n"] == 48
    assert result["stream_view_changes_per_sec"] >= 0
    assert result["stream_p99_alert_to_commit_ms"] > 0
    assert 0.0 <= result["stream_overlap_efficiency"] <= 1.0
    assert result["stream_h2d_bytes"] > 0  # churn deltas crossed the seam
    [(stream_begin, stream_close)] = pairs["stream"]
    assert stream_close["event"] == "stage_end"
    assert stream_begin["timeout_s"] > 0
    assert stream_begin["n"] == 6 * 8  # engine rounds enqueued per path
    assert any(
        e["event"] == "device_memory" and e.get("stage") == "stream"
        for e in events
    )
    assert any(
        e["event"] == "compile_stats" and e.get("stage") == "stream"
        for e in events
    )
    # ISSUE 16 device-telemetry path, same run: the serving lanes measured
    # real activity — fractions in (0, 1] with an explicit "measured"
    # status, the zero-churn soak published as an explicit 0.0 (a
    # measurement, not an absence — perfview's activity-missing flag
    # polices exactly this), and the fleet half's pooled + per-tenant
    # conflict rates from the lanes the lockstep wave carried.
    assert result["activity_status"] == "measured"
    assert 0.0 < result["stream_active_fraction"] <= 1.0
    assert (
        result["stream_active_fraction"]
        <= result["stream_peak_active_fraction"]
        <= 1.0
    )
    assert 0.0 <= result["stream_fast_path_share"] <= 1.0
    assert result["quiescent_active_fraction"] == 0.0
    assert 0.0 <= result["tenant_conflict_rate"] <= 1.0
    assert len(result["tenant_conflict_rates"]) == result["fleet_tenants"]
    assert all(0.0 <= r <= 1.0 for r in result["tenant_conflict_rates"])
    assert 0.0 <= result["fleet_fast_path_share"] <= 1.0
    # ISSUE 12 adversarial-chaos path, same run: the chaos stage resolved
    # B mixed hostile scenarios (Byzantine false alerts, committee crashes,
    # honest churn) through batched fleet dispatches in its own bracketed,
    # budgeted stage — scenarios/sec lands in the emitted JSON with an
    # explicit status marker (never silently absent), zero violations.
    assert result["chaos_status"] == "ramped:4x12"
    assert result["chaos_tenants"] == 4
    assert result["chaos_scenarios_per_sec"] > 0
    assert result["chaos_wall_ms"] > 0
    assert result["chaos_dispatches"] >= 1
    assert result["chaos_families"] >= 1
    [(chaos_begin, chaos_close)] = pairs["chaos"]
    assert chaos_close["event"] == "stage_end"
    assert chaos_begin["timeout_s"] > 0
    assert chaos_begin["n"] == 4  # tenants (hostile scenarios) under test
    assert any(
        e["event"] == "compile_stats" and e.get("stage") == "chaos"
        for e in events
    )
    # ISSUE 15 self-healing path, same run: the recovery stage ran the
    # whole drill — transient failure retried on seeded backoff, simulated
    # kill between waves, checkpoint-cadence writes, deterministic resume
    # — in its own bracketed, budgeted stage with the MTTR and the
    # bit-identity verdict in the emitted JSON, never silently absent.
    assert result["recovery_status"] == "ramped:4x48"
    assert result["recovery_mttr_ms"] > 0
    assert result["recovery_bit_identical"] is True
    assert result["recovery_checkpoints"] >= 1
    assert result["recovery_retries"] >= 1
    assert result["recovery_killed_after_wave"] == 2  # waves//2
    assert result["recovery_resumed_wave"] >= 1
    [(recovery_begin, recovery_close)] = pairs["recovery"]
    assert recovery_close["event"] == "stage_end"
    assert recovery_begin["timeout_s"] > 0
    assert recovery_begin["n"] == 48
    # The supervisor's recovery timeline landed in the SAME ledger.
    recovery_kinds = [
        e["event"] for e in events if e.get("stage") == "recovery"
    ]
    assert "recovery_retry" in recovery_kinds
    assert "recovery_checkpoint" in recovery_kinds
    assert "recovery_resume" in recovery_kinds
    # ISSUE 13 memory path, same run: the hlo_audit stage (begin/end
    # bracketed above with every other stage) emits the state-compaction
    # memory axis end-to-end on CPU — bytes/member under all three
    # layouts, the run's total, the 100k->100M sizing table, and the
    # never-silently-absent mem_status.
    [(mem_begin, mem_close)] = pairs["hlo_audit"]
    assert mem_close["event"] == "stage_end"
    assert mem_begin["timeout_s"] > 0
    assert result["mem_status"]  # never silently absent
    assert 0 < result["bytes_per_member"] < result["bytes_per_member_wide"]
    assert result["bytes_per_member_packed"] < result["bytes_per_member"]
    # bytes_per_member is rounded in the JSON; the total is exact.
    assert abs(
        result["state_bytes_total"] - result["bytes_per_member"] * result["n_members"]
    ) <= result["n_members"]
    sizing = result["mem_sizing"]
    assert set(sizing) == {"100k", "1M", "10M", "100M"}
    for row in sizing.values():
        assert row["compact_gb"] < row["wide_gb"]
    # The 100M sizing is the ROADMAP deliverable: a concrete GB figure.
    assert sizing["100M"]["n"] == 100_000_000
    assert sizing["100M"]["compact_gb"] > 0
    # The audit compiled the compact entrypoints, so the status is the
    # measured one (memory_analysis argument bytes present for the pair).
    assert result["mem_status"] == "live:hlo-audit"
    assert result["hlo_audit"]["step_compact"]["argument_bytes"] < (
        result["hlo_audit"]["step"]["argument_bytes"]
    )


def test_headline_plan_is_never_silently_absent(monkeypatch):
    """ISSUE 9: every branch of the headline policy yields an explicit
    status — unit-pinned so the skipped/suppressed paths don't need their
    own full bench subprocess."""
    for name in ("RAPID_TPU_BENCH_NO_XL", "RAPID_TPU_BENCH_XL",
                 "RAPID_TPU_BENCH_XL_N", "RAPID_TPU_BENCH_XL_BUDGET_S"):
        monkeypatch.delenv(name, raising=False)
    assert bench.headline_plan("tpu", 0.0) == (1_000_000, "live")
    assert bench.headline_plan("cpu", 0.0) == (4096, "ramped:4096")
    monkeypatch.setenv("RAPID_TPU_BENCH_XL_N", "256")
    assert bench.headline_plan("cpu", 0.0) == (256, "ramped:256")
    # Past the XL budget the point is skipped — but NAMED.
    assert bench.headline_plan("tpu", 2000.0) == (0, "skipped-budget")
    # ...unless explicitly forced.
    monkeypatch.setenv("RAPID_TPU_BENCH_XL", "1")
    assert bench.headline_plan("cpu", 2000.0) == (1_000_000, "live")
    monkeypatch.setenv("RAPID_TPU_BENCH_NO_XL", "1")
    assert bench.headline_plan("tpu", 0.0) == (0, "suppressed")


def test_fleet_plan_is_never_silently_absent(monkeypatch):
    """ISSUE 10: every branch of the tenant-fleet policy yields an explicit
    status (the headline_plan discipline) — live at 256x1024 on the
    accelerator, ramped on CPU, skipped-budget past the (shared-default)
    budget, suppressed on request, forced when asked."""
    for name in ("RAPID_TPU_BENCH_NO_FLEET", "RAPID_TPU_BENCH_FLEET",
                 "RAPID_TPU_BENCH_FLEET_B", "RAPID_TPU_BENCH_FLEET_N",
                 "RAPID_TPU_BENCH_FLEET_BUDGET_S",
                 "RAPID_TPU_BENCH_XL_BUDGET_S"):
        monkeypatch.delenv(name, raising=False)
    assert bench.fleet_plan("tpu", 0.0) == (256, 1024, "live")
    assert bench.fleet_plan("cpu", 0.0) == (8, 64, "ramped:8x64")
    monkeypatch.setenv("RAPID_TPU_BENCH_FLEET_B", "4")
    monkeypatch.setenv("RAPID_TPU_BENCH_FLEET_N", "48")
    assert bench.fleet_plan("cpu", 0.0) == (4, 48, "ramped:4x48")
    # Past the budget the point is skipped — but NAMED; the fleet budget
    # defaults to the XL budget so one env override governs both tails.
    assert bench.fleet_plan("tpu", 2000.0) == (0, 0, "skipped-budget")
    monkeypatch.setenv("RAPID_TPU_BENCH_FLEET_BUDGET_S", "3000")
    assert bench.fleet_plan("tpu", 2000.0)[2] == "live"
    # ...and forcing runs it anywhere, at the live scale.
    monkeypatch.setenv("RAPID_TPU_BENCH_FLEET_BUDGET_S", "1")
    monkeypatch.setenv("RAPID_TPU_BENCH_FLEET", "1")
    assert bench.fleet_plan("cpu", 2000.0) == (4, 48, "live")
    monkeypatch.setenv("RAPID_TPU_BENCH_NO_FLEET", "1")
    assert bench.fleet_plan("tpu", 0.0) == (0, 0, "suppressed")


def test_stream_plan_is_never_silently_absent(monkeypatch):
    """ISSUE 11: every branch of the streaming-serving policy yields an
    explicit status (the headline_plan discipline) — 64 waves at N=4096 on
    the accelerator, ramped on CPU, skipped-budget past the (shared-default)
    budget, suppressed on request, forced when asked."""
    for name in ("RAPID_TPU_BENCH_NO_STREAM", "RAPID_TPU_BENCH_STREAM",
                 "RAPID_TPU_BENCH_STREAM_WAVES", "RAPID_TPU_BENCH_STREAM_N",
                 "RAPID_TPU_BENCH_STREAM_BUDGET_S",
                 "RAPID_TPU_BENCH_XL_BUDGET_S"):
        monkeypatch.delenv(name, raising=False)
    assert bench.stream_plan("tpu", 0.0) == (64, 4096, "live")
    assert bench.stream_plan("cpu", 0.0) == (12, 96, "ramped:12x96")
    monkeypatch.setenv("RAPID_TPU_BENCH_STREAM_WAVES", "6")
    monkeypatch.setenv("RAPID_TPU_BENCH_STREAM_N", "48")
    assert bench.stream_plan("cpu", 0.0) == (6, 48, "ramped:6x48")
    # Past the budget the point is skipped — but NAMED; the stream budget
    # defaults to the XL budget so one env override governs all three tails.
    assert bench.stream_plan("tpu", 2000.0) == (0, 0, "skipped-budget")
    monkeypatch.setenv("RAPID_TPU_BENCH_STREAM_BUDGET_S", "3000")
    assert bench.stream_plan("tpu", 2000.0)[2] == "live"
    # ...and forcing runs it anywhere, at the env-resolved scale.
    monkeypatch.setenv("RAPID_TPU_BENCH_STREAM_BUDGET_S", "1")
    monkeypatch.setenv("RAPID_TPU_BENCH_STREAM", "1")
    assert bench.stream_plan("cpu", 2000.0) == (6, 48, "live")
    monkeypatch.setenv("RAPID_TPU_BENCH_NO_STREAM", "1")
    assert bench.stream_plan("tpu", 0.0) == (0, 0, "suppressed")


def test_chaos_plan_is_never_silently_absent(monkeypatch):
    """ISSUE 12: every branch of the adversarial-chaos policy yields an
    explicit status (the headline_plan discipline) — 256 mixed hostile
    scenarios per fleet on the accelerator, ramped on CPU, skipped-budget
    past the (shared-default) budget, suppressed on request, forced when
    asked."""
    for name in ("RAPID_TPU_BENCH_NO_CHAOS", "RAPID_TPU_BENCH_CHAOS",
                 "RAPID_TPU_BENCH_CHAOS_B", "RAPID_TPU_BENCH_CHAOS_BUDGET_S",
                 "RAPID_TPU_BENCH_XL_BUDGET_S"):
        monkeypatch.delenv(name, raising=False)
    assert bench.chaos_plan("tpu", 0.0) == (256, "live")
    assert bench.chaos_plan("cpu", 0.0) == (12, "ramped:12x12")
    monkeypatch.setenv("RAPID_TPU_BENCH_CHAOS_B", "4")
    assert bench.chaos_plan("cpu", 0.0) == (4, "ramped:4x12")
    # Past the budget the stage is skipped — but NAMED; the chaos budget
    # defaults to the XL budget so one env override governs every tail.
    assert bench.chaos_plan("tpu", 2000.0) == (0, "skipped-budget")
    monkeypatch.setenv("RAPID_TPU_BENCH_CHAOS_BUDGET_S", "3000")
    assert bench.chaos_plan("tpu", 2000.0)[1] == "live"
    # ...and forcing runs it anywhere, at the env-resolved scale.
    monkeypatch.setenv("RAPID_TPU_BENCH_CHAOS_BUDGET_S", "1")
    monkeypatch.setenv("RAPID_TPU_BENCH_CHAOS", "1")
    assert bench.chaos_plan("cpu", 2000.0) == (4, "live")
    monkeypatch.setenv("RAPID_TPU_BENCH_NO_CHAOS", "1")
    assert bench.chaos_plan("tpu", 0.0) == (0, "suppressed")


def test_recovery_plan_is_never_silently_absent(monkeypatch):
    """ISSUE 15: every branch of the self-healing drill policy yields an
    explicit status (the headline_plan discipline) — N=4096 x 16 waves on
    the accelerator, ramped on CPU, skipped-budget past the
    (shared-default) budget, suppressed on request, forced when asked."""
    for name in ("RAPID_TPU_BENCH_NO_RECOVERY", "RAPID_TPU_BENCH_RECOVERY",
                 "RAPID_TPU_BENCH_RECOVERY_N",
                 "RAPID_TPU_BENCH_RECOVERY_WAVES",
                 "RAPID_TPU_BENCH_RECOVERY_BUDGET_S",
                 "RAPID_TPU_BENCH_XL_BUDGET_S"):
        monkeypatch.delenv(name, raising=False)
    assert bench.recovery_plan("tpu", 0.0) == (4096, 16, "live")
    assert bench.recovery_plan("cpu", 0.0) == (64, 6, "ramped:6x64")
    monkeypatch.setenv("RAPID_TPU_BENCH_RECOVERY_N", "32")
    monkeypatch.setenv("RAPID_TPU_BENCH_RECOVERY_WAVES", "4")
    assert bench.recovery_plan("cpu", 0.0) == (32, 4, "ramped:4x32")
    # Past the budget the stage is skipped — but NAMED; the recovery
    # budget defaults to the XL budget so one override governs every tail.
    assert bench.recovery_plan("tpu", 2000.0) == (0, 0, "skipped-budget")
    monkeypatch.setenv("RAPID_TPU_BENCH_RECOVERY_BUDGET_S", "3000")
    assert bench.recovery_plan("tpu", 2000.0)[2] == "live"
    # ...and forcing runs it anywhere, at the env-resolved scale.
    monkeypatch.setenv("RAPID_TPU_BENCH_RECOVERY_BUDGET_S", "1")
    monkeypatch.setenv("RAPID_TPU_BENCH_RECOVERY", "1")
    assert bench.recovery_plan("cpu", 2000.0) == (32, 4, "live")
    monkeypatch.setenv("RAPID_TPU_BENCH_NO_RECOVERY", "1")
    assert bench.recovery_plan("tpu", 0.0) == (0, 0, "suppressed")


def test_activity_status_is_never_silently_absent():
    """ISSUE 16: every branch of the device-telemetry status policy yields
    an explicit marker — "measured" iff the stream stage fetched a numeric
    active fraction, the stage's own skip reason otherwise — unit-pinned so
    the skipped/suppressed paths don't need their own bench subprocess."""
    assert bench.activity_status(
        {"stream_active_fraction": 0.0417}, "ramped:6x48"
    ) == "measured"
    # 0.0 is a measurement (the quiescent soak), never an absence.
    assert bench.activity_status(
        {"stream_active_fraction": 0.0}, "ramped:6x48"
    ) == "measured"
    assert bench.activity_status({}, "ramped:12x96") == "ramped:12x96"
    assert bench.activity_status({}, "skipped-budget") == "skipped-budget"
    assert bench.activity_status({}, "suppressed") == "suppressed"
    assert bench.activity_status(
        {"stream_active_fraction": None}, "suppressed"
    ) == "suppressed"


def test_memory_report_status_is_never_silently_absent():
    """ISSUE 13: memory_report is pure over (audit table, geometry) and
    always yields a mem_status — measured when the audit carries argument
    bytes for the wide+compact step pair, an explicit computed:<why>
    marker otherwise (audit errored, absent, or lacking memory analysis)."""
    geometry = dict(n=1024, k_rings=10, cohorts=8)
    live = bench.memory_report(
        {"step": {"argument_bytes": 1000}, "step_compact": {"argument_bytes": 600}},
        **geometry,
    )
    assert live["mem_status"] == "live:hlo-audit"
    assert 0 < live["bytes_per_member"] < live["bytes_per_member_wide"]
    assert set(live["mem_sizing"]) == {"100k", "1M", "10M", "100M"}

    errored = bench.memory_report({"error": "needs 8 devices"}, **geometry)
    assert errored["mem_status"].startswith("computed:")
    assert errored["bytes_per_member"] == live["bytes_per_member"]

    partial = bench.memory_report(
        {"step": {"argument_bytes": None}, "step_compact": {}}, **geometry
    )
    assert partial["mem_status"] == "computed:audit-lacks-step-memory"

    # The sizing ladder re-derives the policy per N: the 100M row's
    # bytes/member EXCEEDS the small-N row's (index lanes re-widen to
    # int32 past 32k slots) — the table is honest, not an extrapolation.
    assert (
        live["mem_sizing"]["100M"]["bytes_per_member"]
        > live["bytes_per_member"]
    )


def test_parse_scale_spellings():
    assert bench._parse_scale("10M") == 10_000_000
    assert bench._parse_scale("10m") == 10_000_000
    assert bench._parse_scale("250k") == 250_000
    assert bench._parse_scale("4096") == 4096
    assert bench._parse_scale("gibberish") == 0


def test_ledger_event_vocabulary_is_enforced_in_bench(tmp_path):
    # The runtime guard behind the lint rule: bench cannot invent events.
    from rapid_tpu.utils.ledger import RunLedger

    ledger = RunLedger(str(tmp_path / "l.jsonl"))
    with pytest.raises(TypeError):
        ledger.emit("made_up_event")
    ledger.close()


def test_stage_timeouts_table_covers_all_stages():
    from rapid_tpu.utils.ledger import STAGE_NAMES

    assert set(bench.STAGE_TIMEOUTS_S) == set(STAGE_NAMES)
    assert all(v > 0 for v in bench.STAGE_TIMEOUTS_S.values())


def test_parse_args_flags_and_env_aliases(monkeypatch):
    monkeypatch.delenv("RAPID_TPU_BENCH_PROFILE", raising=False)
    args = bench._parse_args([])
    assert args.profile is None and args.ledger is None
    args = bench._parse_args(["--profile", "/tmp/prof", "--ledger", "x.jsonl"])
    assert args.profile == "/tmp/prof" and args.ledger == "x.jsonl"
    monkeypatch.setenv("RAPID_TPU_BENCH_PROFILE", "/tmp/envprof")
    assert bench._parse_args([]).profile == "/tmp/envprof"


def test_main_without_a_chip_or_an_explicit_cpu_request_fails(
    tmp_path, monkeypatch, capsys
):
    """One process, chip or fail: on a non-TPU backend ``main`` returns
    nonzero unless the CALLER set JAX_PLATFORMS=cpu — the ledger names the
    stage it stopped in and no metric line is printed. (In-process: the
    session's backend is the CPU mesh, so only the variable is removed.)"""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    ledger_path = tmp_path / "ledger.jsonl"
    assert bench.main(["--ledger", str(ledger_path)]) != 0
    captured = capsys.readouterr()
    assert "not 'tpu'" in captured.err and "JAX_PLATFORMS=cpu" in captured.err
    assert captured.out == ""
    events, _ = read_ledger(str(ledger_path))
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_begin" and kinds[-1] == "run_fail"
    [failed] = [e for e in events if e["event"] == "stage_fail"]
    assert failed["stage"] == "devices_init"
    assert "metric" not in kinds


def test_bench_is_one_process():
    # The chip belongs to the process that imports jax: bench starts no
    # child, so the module has no use for subprocess at all.
    assert not hasattr(bench, "subprocess")
    assert "Popen" not in Path(BENCH).read_text()
