"""Pure helpers of bench.py, unit-pinned: the delivery-kernel tile-width
resolution (``_autotuned_lanes``), the derived throughput metrics with their
plausibility bounds, and the embedded compiled-program audit table.
"""

import json

import pytest

import bench


def test_autotuned_lanes_resolution(tmp_path, monkeypatch):
    # Width resolution order: env override first; else newest committed
    # autotune evidence, nearest measured shape; else the default. Garbage
    # lines and non-TPU or insane widths never poison the choice.
    for name in ("RAPID_TPU_BENCH_LANES", "RAPID_TPU_BENCH_LANES_1M"):
        monkeypatch.delenv(name, raising=False)
    evdir = tmp_path / "evidence" / "round9"
    evdir.mkdir(parents=True)
    (evdir / "autotune.jsonl").write_text(
        json.dumps({"platform": "tpu", "best_width": 999}) + "\n"  # no shape: skipped
        + json.dumps({"platform": "tpu", "shape": [64, 100_000], "best_width": 256}) + "\n"
        + json.dumps({"platform": "tpu", "shape": [8, 1_000_000], "best_width": 512}) + "\n"
        + json.dumps({"platform": "cpu", "shape": [64, 100_000], "best_width": 1024}) + "\n"
        + json.dumps({"platform": "tpu", "shape": [8, 500_000], "best_width": 7}) + "\n"
        + "not json{\n"
    )
    monkeypatch.setattr(
        bench.glob, "glob", lambda pattern: [str(evdir / "autotune.jsonl")]
    )
    MAIN, XL = "RAPID_TPU_BENCH_LANES", "RAPID_TPU_BENCH_LANES_1M"
    assert bench._autotuned_lanes(100_000, MAIN) == 256   # exact shape
    assert bench._autotuned_lanes(90_000, MAIN) == 256    # nearest shape
    assert bench._autotuned_lanes(1_000_000, XL) == 512
    # The sweep plumbs per-point widths through the MAIN env at any N.
    monkeypatch.setenv(MAIN, "1024")
    assert bench._autotuned_lanes(100_000, MAIN) == 1024  # env wins
    assert bench._autotuned_lanes(1_000_000, MAIN) == 1024
    monkeypatch.setenv(XL, "128")
    assert bench._autotuned_lanes(1_000_000, XL) == 128


def test_autotuned_lanes_shape_proximity_guard(tmp_path, monkeypatch):
    # A tuned width only transfers to shapes within 4x of where it was
    # measured: a 2K smoke run must not inherit the 100K-tuned width (the
    # tiling economics don't carry), but 25K-400K legitimately may.
    for name in ("RAPID_TPU_BENCH_LANES", "RAPID_TPU_BENCH_LANES_1M"):
        monkeypatch.delenv(name, raising=False)
    evdir = tmp_path / "evidence" / "round9"
    evdir.mkdir(parents=True)
    (evdir / "autotune.jsonl").write_text(
        json.dumps({"platform": "tpu", "shape": [64, 100_000], "best_width": 512}) + "\n"
    )
    monkeypatch.setattr(
        bench.glob, "glob", lambda pattern: [str(evdir / "autotune.jsonl")]
    )
    MAIN = "RAPID_TPU_BENCH_LANES"
    assert bench._autotuned_lanes(2_000, MAIN) == 128       # far below: default
    assert bench._autotuned_lanes(25_000, MAIN) == 512      # 4x boundary: applies
    assert bench._autotuned_lanes(400_000, MAIN) == 512     # 4x boundary: applies
    assert bench._autotuned_lanes(1_000_000, MAIN) == 128   # far above: default
    monkeypatch.setenv(MAIN, "256")
    assert bench._autotuned_lanes(2_000, MAIN) == 256       # env always wins


def test_autotuned_lanes_eligibility_before_nearest(tmp_path, monkeypatch):
    # Eligibility (4x window) filters BEFORE nearest-shape selection: at
    # N=450K with 100K and 1M both tuned, 100K is nearer by absolute
    # distance but out of window — the in-window 1M width must apply, not
    # the default.
    for name in ("RAPID_TPU_BENCH_LANES", "RAPID_TPU_BENCH_LANES_1M"):
        monkeypatch.delenv(name, raising=False)
    evdir = tmp_path / "evidence" / "round9"
    evdir.mkdir(parents=True)
    (evdir / "autotune.jsonl").write_text(
        json.dumps({"platform": "tpu", "shape": [64, 100_000], "best_width": 512}) + "\n"
        + json.dumps({"platform": "tpu", "shape": [8, 1_000_000], "best_width": 256}) + "\n"
    )
    monkeypatch.setattr(
        bench.glob, "glob", lambda pattern: [str(evdir / "autotune.jsonl")]
    )
    MAIN = "RAPID_TPU_BENCH_LANES"
    assert bench._autotuned_lanes(450_000, MAIN) == 256   # only 1M in window
    assert bench._autotuned_lanes(200_000, MAIN) == 512   # both in window; 100K nearer by ratio


def test_autotuned_lanes_defaults_without_evidence(monkeypatch):
    for name in ("RAPID_TPU_BENCH_LANES", "RAPID_TPU_BENCH_LANES_1M"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(bench.glob, "glob", lambda pattern: [])
    assert bench._autotuned_lanes(100_000, "RAPID_TPU_BENCH_LANES") == 128


# ---------------------------------------------------------------------------
# Derived bench metrics: units audited, plausibility bounds pinned
# ---------------------------------------------------------------------------


def test_derived_metrics_formulas_at_engine_grain():
    # The default workload at the r03 snapshot's wall-clock. The engine
    # delivers per COHORT (C delivered-bit sets per alert), not per member:
    # the old N-multiplied formula produced the implausible 4.96e10/s figure
    # flagged across BENCH_r03-r05.
    d = bench.derived_metrics(
        n=100_000, n_join=2500, n_crash=2500, k_rings=10, cohorts=64,
        value_ms=100.875,
    )
    assert d["alerts_fired"] == 5000 * 10
    assert d["alerts_per_sec"] == round(50_000 / 0.100875, 0)
    assert d["alert_deliveries_per_sec"] == round(50_000 * 64 / 0.100875, 0)
    # The delivery rate is alerts x cohorts — never x N (each rate rounds
    # independently, so the identity holds to rounding slack).
    assert abs(d["alert_deliveries_per_sec"] - 64 * d["alerts_per_sec"]) <= 64


@pytest.mark.parametrize("value_ms", [10.0, 100.875, 500.0, 60_000.0])
def test_derived_metrics_plausibility_bounds(value_ms):
    # Any resolution between 10 ms (4x the r03 hardware number — far below
    # any credible future point) and a minute at the default workload must
    # yield physically plausible rates: alerts bounded by churn x K, and
    # deliveries under 1e9/s (no chip or network moves more distinct alert
    # deliveries than that at these Ns — the 4.96e10 figure could never
    # have passed this pin).
    d = bench.derived_metrics(
        n=100_000, n_join=2500, n_crash=2500, k_rings=10, cohorts=64,
        value_ms=value_ms,
    )
    assert 0 < d["alerts_per_sec"] <= 5_000 * 10 * 1000  # >= 1 ms resolution
    assert d["alert_deliveries_per_sec"] < 1e9
    assert abs(d["alert_deliveries_per_sec"] - d["alerts_per_sec"] * 64) <= 64


def test_derived_metrics_reject_degenerate_wallclock():
    with pytest.raises(ValueError, match="positive"):
        bench.derived_metrics(
            n=100, n_join=1, n_crash=1, k_rings=10, cohorts=4, value_ms=0.0
        )



def test_hlo_audit_summary_embeds_per_entrypoint_budget_table():
    # The bench's hlo_audit stage embeds this table in the metric JSON:
    # one row per registered entrypoint with the collective counts the
    # perfview trajectory diffs (hlo-drift), plus temp memory and donation
    # outcomes. Compiles ride the process-wide session cache shared with
    # the staticcheck gate, so this costs nothing extra in a full session.
    table = bench.hlo_audit_summary()
    assert "error" not in table, table
    assert {"step", "run_to_decision", "run_until_membership", "sync",
            "step_compact", "step_telem", "step_trace",
            "sharded_step", "sharded_step_telem", "sharded_wave",
            "sharded2d_wave",
            "fleet3d_step", "fleet3d_wave"} == set(table)
    for name, row in table.items():
        assert set(row) == {
            "collectives", "collective_bytes", "hot_loop_collectives",
            "hot_loop_bytes", "temp_bytes", "argument_bytes",
            "donation_dropped",
        }, name
        assert row["donation_dropped"] == 0, name
    # The compaction saving is visible in the embedded table (the bench's
    # memory_report keys its mem_status off exactly this pair).
    assert (
        table["step_compact"]["argument_bytes"]
        < table["step"]["argument_bytes"]
    )
    # Sharded programs communicate; single-device ones must not.
    assert table["sharded_wave"]["hot_loop_collectives"] > 0
    assert table["step"]["collectives"] == 0
