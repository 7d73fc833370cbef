"""Device-engine observability tier: compile-event capture, per-dispatch
latency histograms, transfer-byte accounting, device memory stats — surfaced
through the unified ``telemetry_snapshot()`` / ``prometheus_text()`` contract
with the engine metric names pinned as a golden vocabulary (renaming one is
an API break for every scrape config, same rule as the host tier's).
"""

import json
import sys
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import clustertop  # noqa: E402  — tools/clustertop.py, the live dashboard

from rapid_tpu.models.virtual_cluster import SMALL_WAVE_SLOTS, VirtualCluster  # noqa: E402
from rapid_tpu.utils import engine_telemetry, exposition  # noqa: E402
from rapid_tpu.utils.histogram import NUM_BUCKETS, LogHistogram  # noqa: E402


def _cluster(n=16, cohorts=2):
    vc = VirtualCluster.create(
        n, k=3, h=3, l=1, cohorts=cohorts, fd_threshold=2, seed=0
    )
    vc.assign_cohorts_roundrobin()
    return vc


#: The engine scrape's complete metric-name vocabulary (host KNOWN_COUNTERS
#: zero-fill + the engine tier). This list is an API — see the host golden
#: list in tests/test_observability.py for the contract.
GOLDEN_ENGINE_METRIC_NAMES = [
    "rapid_alert_batches_redelivered_total",
    "rapid_alert_batches_sent_total",
    "rapid_alerts_enqueued_total",
    "rapid_alerts_received_total",
    "rapid_catch_up_wedged_total",
    "rapid_classic_rounds_started_total",
    "rapid_config_beacons_sent_total",
    "rapid_config_catch_ups_total",
    "rapid_config_pull_unchanged_served_total",
    "rapid_config_sync_unchanged_total",
    "rapid_configuration_id",
    "rapid_decision_missing_joiner_uuid_total",
    # The time a membership change was pending (ISSUE 54): one histogram,
    # zero-filled until the first change closes.
    "rapid_engine_change_ms_bucket",
    "rapid_engine_change_ms_count",
    "rapid_engine_change_ms_sum",
    "rapid_engine_compile_cache_requests_total",
    "rapid_engine_compile_ms_bucket",
    "rapid_engine_compile_ms_count",
    "rapid_engine_compile_ms_sum",
    "rapid_engine_compiles_total",
    "rapid_engine_convergence_steps_total",
    "rapid_engine_cuts_committed_total",
    "rapid_engine_d2h_bytes_total",
    "rapid_engine_device_bytes_in_use",
    "rapid_engine_device_peak_bytes",
    "rapid_engine_dispatch_ms_bucket",
    "rapid_engine_dispatch_ms_count",
    "rapid_engine_dispatch_ms_sum",
    "rapid_engine_dispatches_total",
    "rapid_engine_edge_mask_builds_total",
    "rapid_engine_edge_mask_reuses_total",
    "rapid_engine_h2d_bytes_total",
    "rapid_engine_live_buffer_bytes",
    "rapid_engine_live_buffers",
    "rapid_engine_persistent_cache_hits_total",
    "rapid_engine_persistent_cache_misses_total",
    # Who took set-up's seconds (ISSUE 35): the jax pipeline by stage, and
    # the constructors' set-up stages. No per-program series.
    "rapid_engine_pipeline_seconds_total",
    "rapid_engine_setup_seconds_total",
    "rapid_engine_steps_total",
    "rapid_kicked_total",
    "rapid_membership_size",
    "rapid_node_health",
    "rapid_proposals_announced_total",
    "rapid_view_changes_total",
]


def test_engine_prometheus_names_are_golden():
    vc = _cluster()
    vc.crash([3])
    vc.step()
    vc.run_to_decision(max_steps=32)
    vc.sync()
    names = exposition.metric_names(vc.prometheus_text())
    assert names == GOLDEN_ENGINE_METRIC_NAMES


def test_snapshot_engine_section_shape_and_serializable():
    vc = _cluster()
    snap = vc.telemetry_snapshot()
    engine = snap["engine"]
    assert engine["n"] == 16 and engine["cohorts"] == 2
    assert set(engine["compile"]) == {
        "compiles", "compile_ms", "persistent_cache_hits",
        "persistent_cache_misses", "cache_requests",
        "pipeline_s", "by_span", "by_program", "recent",
    }
    assert set(engine["compile"]["pipeline_s"]) == {
        "trace", "lower", "load", "cache_retrieval", "cache_saved",
    }
    assert "outermost" in engine["setup"]
    assert set(engine["memory"]) == {
        "live_buffers", "live_buffer_bytes",
        "device_bytes_in_use", "device_peak_bytes",
    }
    json.dumps(snap)  # the --metrics-dump / clustertop artifact


def test_compile_events_are_captured():
    # A never-before-seen shape forces a fresh XLA compile; the process-wide
    # collector must see it (count + duration histogram), and CompileDelta
    # must attribute it to the bracketed phase.
    assert engine_telemetry.install() is True
    probe = jax.jit(lambda x: (x * 3 + 1).sum())
    with engine_telemetry.CompileDelta() as delta:
        probe(jnp.arange(173))  # unusual length: not a cached executable
    assert delta.delta["compiles"] >= 1
    assert delta.delta["compile_ms"] > 0
    snap = engine_telemetry.compile_snapshot()
    assert snap["compiles"] >= 1
    assert snap["compile_ms"]["count"] == snap["compiles"]


# ---------------------------------------------------------------------------
# Set-up from the inside (ISSUE 35): every pipeline event goes to the program
# that caused it and to the span it ran under; the constructors' stages
# ---------------------------------------------------------------------------

_STAGE_SUMS = ("trace_s", "lower_s", "load_s")


def _fresh_program(length):
    """A jit nobody has called, on a shape nobody has used: one trace, one
    lowering and one compile when it is first called."""

    def probe_fresh_shape(x):
        return (x * 5 + 2).sum()

    return jax.jit(probe_fresh_shape), jnp.arange(length)


def _row_delta(before, after, table, key):
    was = before[table].get(key, {})
    return {f: v - was.get(f, 0) for f, v in after[table][key].items()}


def test_compile_inside_a_dispatch_phase_is_named_by_program_and_phase():
    vc = _cluster()
    probe, x = _fresh_program(181)
    before = engine_telemetry.compile_snapshot()
    with vc._dispatch("run_to_decision"):
        probe(x)
    after = engine_telemetry.compile_snapshot()
    span = _row_delta(before, after, "by_span", "run_to_decision")
    program = _row_delta(before, after, "by_program", "probe_fresh_shape")
    for row in (span, program):
        assert all(row[field] > 0 for field in _STAGE_SUMS), row
    assert span["programs"] == program["count"] == 1
    name, seconds, where, from_cache = after["recent"][-1]
    assert (name, where) == ("probe_fresh_shape", "run_to_decision")
    assert seconds == pytest.approx(program["load_s"])
    # The process totals moved by exactly what the two tables filed.
    for stage, field in zip(("trace", "lower", "load"), _STAGE_SUMS):
        moved = after["pipeline_s"][stage] - before["pipeline_s"][stage]
        assert moved == pytest.approx(span[field])
    assert after["compiles"] - before["compiles"] == 1
    # ... and both drivers' scrapes carry it.
    assert "probe_fresh_shape" in vc.telemetry_snapshot()["engine"]["compile"]["by_program"]
    fleet_compile = _fleet().telemetry_snapshot()["engine"]["compile"]
    assert fleet_compile["by_span"]["run_to_decision"]["programs"] >= 1
    assert ["probe_fresh_shape", seconds, "run_to_decision", from_cache] in fleet_compile["recent"]


def test_compile_outside_any_span_is_filed_outside():
    engine_telemetry.install()
    probe, x = _fresh_program(183)
    before = engine_telemetry.compile_snapshot()
    probe(x)
    after = engine_telemetry.compile_snapshot()
    outside = _row_delta(before, after, "by_span", engine_telemetry.OUTSIDE)
    assert outside["programs"] == 1
    assert all(outside[field] > 0 for field in _STAGE_SUMS)
    assert after["recent"][-1][2] == engine_telemetry.OUTSIDE


def test_span_stack_is_restored_under_nesting_and_after_an_exception():
    from rapid_tpu.utils.dispatch import setup_stage

    vc = _cluster()
    spans = engine_telemetry._COLLECTOR.spans
    assert spans == []
    with setup_stage("create"):
        with vc._dispatch("stream_enqueue", wave=3):
            assert spans == ["setup.create", "stream_enqueue"]
        assert spans == ["setup.create"]
        with pytest.raises(RuntimeError):
            with vc._dispatch("step"):
                with setup_stage("create.state"):
                    assert spans == ["setup.create", "step", "setup.create.state"]
                    raise RuntimeError("inside two spans")
        assert spans == ["setup.create"]
    assert spans == []
    # The failed blocks were still timed: the stage and the phase both count.
    assert engine_telemetry.setup_snapshot()["create.state"]["count"] >= 1
    assert vc.metrics.phase_timings["engine_dispatch"]["step"].count == 1


def test_unregistered_setup_stage_raises_at_write_time():
    from rapid_tpu.utils.dispatch import ENGINE_SETUP_STAGES, setup_stage

    with pytest.raises(ValueError, match="unregistered engine set-up stage"):
        with setup_stage("warm_up"):
            pass
    assert "warm_up" not in engine_telemetry.setup_snapshot()
    assert len(ENGINE_SETUP_STAGES) <= 8
    # A dotted stage names a registered parent.
    assert all(s.rsplit(".", 1)[0] in ENGINE_SETUP_STAGES for s in ENGINE_SETUP_STAGES)


def _stage_deltas(before, after):
    return {
        stage: {
            field: value - before.get(stage, {}).get(field, 0)
            for field, value in row.items()
        }
        for stage, row in after.items()
    }


def test_constructors_leave_nested_stage_sums():
    before = engine_telemetry.setup_snapshot()
    _cluster()
    mid = engine_telemetry.setup_snapshot()
    made = _stage_deltas(before, mid)
    assert made["create"]["count"] == made["outermost"]["count"] == 1
    assert made["create.keys"]["count"] == made["create.state"]["count"] == 1
    assert 0 < made["create.keys"]["wall_s"] + made["create.state"]["wall_s"] <= made["create"]["wall_s"]
    assert made["outermost"]["wall_s"] == pytest.approx(made["create"]["wall_s"])

    _fleet(b=4)
    made = _stage_deltas(mid, engine_telemetry.setup_snapshot())
    # Four creates inside the fleet's loop: counted as stages, and inside
    # fleet_create, which alone is outermost.
    assert made["create"]["count"] == 4 and made["fleet_create"]["count"] == 1
    assert made["outermost"]["count"] == 1
    assert made["outermost"]["wall_s"] == pytest.approx(made["fleet_create"]["wall_s"])
    assert made["create"]["wall_s"] <= made["fleet_create.tenants"]["wall_s"]
    assert (
        made["fleet_create.tenants"]["wall_s"] + made["fleet_create.stack"]["wall_s"]
        <= made["fleet_create"]["wall_s"]
    )


def _feed(collector, stage, name, seconds, inside=()):
    """One pipeline interval as jax records it: the start scalar, whatever
    runs inside, the duration."""
    event = {v: k for k, v in engine_telemetry._PIPELINE_EVENTS.items()}[stage]
    collector.on_start(event, 0.0, fun_name=name)
    for inner in inside:
        _feed(collector, *inner)
    collector.on_duration(event, seconds, fun_name=name)


def test_by_program_stays_bounded_and_other_conserves_the_sums():
    collector = engine_telemetry._CompileCollector()
    rows = engine_telemetry.PROGRAM_ROWS
    names = [f"program_{i}" for i in range(5 * rows)]
    for i, name in enumerate(names):
        _feed(collector, "trace", name, 0.001 * (i + 1))
        _feed(collector, "lower", f"jit({name})", 0.002 * (i + 1))
        _feed(collector, "load", f"jit({name})", 0.003 * (i + 1))
        assert len(collector.by_program) <= 2 * rows + 1
    snap = collector.snapshot()
    table = snap["by_program"]
    assert len(table) == rows + 1 and engine_telemetry.OTHER in table
    # The largest stayed under their own names, whole.
    assert table[names[-1]] == {
        "trace_s": pytest.approx(0.001 * len(names)), "lower_s": pytest.approx(0.002 * len(names)),
        "load_s": pytest.approx(0.003 * len(names)), "count": 1, "from_cache": 0,
    }
    for stage, field in zip(("trace", "lower", "load"), _STAGE_SUMS):
        assert sum(row[field] for row in table.values()) == pytest.approx(snap["pipeline_s"][stage])
    assert sum(row["count"] for row in table.values()) == snap["compiles"] == len(names)
    assert len(snap["recent"]) == engine_telemetry.RECENT_LOADS
    json.dumps(snap)


def test_nested_pipeline_intervals_count_every_second_once():
    collector = engine_telemetry._CompileCollector()
    depth = collector.push_span("fleet_wave")
    # outer's trace (1.0 s) holds inner's trace (0.4 s), which holds an eager
    # helper's lowering and compile (0.1 + 0.2 s): trace time is 0.7 s, all
    # outer's; the helper keeps its own.
    _feed(collector, "trace", "outer", 1.0, inside=[
        ("trace", "inner", 0.4, [("lower", "jit(helper)", 0.1), ("load", "jit(helper)", 0.2)]),
    ])
    collector.pop_span(depth)
    snap = collector.snapshot()
    assert snap["pipeline_s"]["trace"] == pytest.approx(0.7)
    assert snap["pipeline_s"]["lower"] == pytest.approx(0.1)
    assert snap["pipeline_s"]["load"] == pytest.approx(0.2)
    assert snap["by_program"]["outer"]["trace_s"] == pytest.approx(0.7)
    assert "inner" not in snap["by_program"]
    assert snap["by_program"]["helper"]["load_s"] == pytest.approx(0.2)
    assert snap["by_span"] == {"fleet_wave": {
        "trace_s": pytest.approx(0.7), "lower_s": pytest.approx(0.1),
        "load_s": pytest.approx(0.2), "programs": 1,
    }}
    assert collector.spans == []
    # An end without its start (a jax that records no start scalar) counts whole.
    collector.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.25, fun_name="late")
    assert collector.snapshot()["by_span"][engine_telemetry.OUTSIDE]["trace_s"] == pytest.approx(0.25)


def test_a_load_knows_whether_the_persistent_cache_served_it():
    collector = engine_telemetry._CompileCollector()
    load = "/jax/core/compile/backend_compile_duration"
    collector.on_event("/jax/compilation_cache/compile_requests_use_cache")
    collector.on_event("/jax/compilation_cache/cache_hits")
    collector.on_duration("/jax/compilation_cache/compile_time_saved_sec", 3.5)
    collector.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    collector.on_duration(load, 0.6, fun_name="jit(served)")
    collector.on_event("/jax/compilation_cache/compile_requests_use_cache")
    collector.on_duration(load, 2.0, fun_name="jit(compiled)")
    collector.on_duration(load, 0.1, fun_name="jit(cache_not_asked)")
    snap = collector.snapshot()
    assert [event[3] for event in snap["recent"]] == [True, False, None]
    assert snap["by_program"]["served"]["from_cache"] == 1
    assert snap["by_program"]["compiled"]["from_cache"] == 0
    assert snap["pipeline_s"]["cache_retrieval"] == 0.5 and snap["pipeline_s"]["cache_saved"] == 3.5
    assert snap["persistent_cache_hits"] == 1 and snap["cache_requests"] == 2
    # An event this jax does not record reads 0 and an unknown one is dropped.
    collector.on_duration("/jax/some/other_duration", 9.0, fun_name="x")
    assert collector.snapshot()["pipeline_s"] == snap["pipeline_s"]


def test_pipeline_and_setup_series_carry_stage_labels_and_no_program_label():
    vc = _cluster()
    text = vc.prometheus_text()
    for stage in ("trace", "lower", "load", "cache_retrieval"):
        assert f'rapid_engine_pipeline_seconds_total{{node="virtual-cluster/16",stage="{stage}"}}' in text
    assert 'rapid_engine_setup_seconds_total{node="virtual-cluster/16",stage="outermost"}' in text
    assert 'stage="create.state"' in text
    assert "probe_fresh_shape" not in text and "fun_name" not in text and "cache_saved" not in text
    # A snapshot written before the sums existed still renders both families.
    legacy = vc.telemetry_snapshot()
    legacy["engine"]["compile"] = {"compiles": 3}
    del legacy["engine"]["setup"]
    old = exposition.prometheus_text(legacy)
    assert 'rapid_engine_pipeline_seconds_total{node="virtual-cluster/16",stage="load"} 0' in old
    assert 'rapid_engine_setup_seconds_total{node="virtual-cluster/16",stage="outermost"} 0' in old


def test_dispatch_histogram_is_bounded_and_per_entrypoint():
    vc = _cluster()
    vc.crash([3])
    for _ in range(40):
        vc.step()
    vc.run_to_decision(max_steps=8)
    family = vc.metrics.phase_timings["engine_dispatch"]
    # Latencies land in the shared bounded instrument, keyed by entrypoint.
    assert isinstance(family["step"], LogHistogram)
    # ... and the injection is a driver operation like any other.
    assert set(family) <= {
        "step", "run_to_decision", "run_until_membership", "sync", "inject_crash"}
    assert family["step"].count == 40 and family["inject_crash"].count == 1
    summary = family["step"].summary()
    # Bounded memory: the summary is O(NUM_BUCKETS) however many dispatches
    # were recorded, and conserves the sample count.
    assert len(summary["buckets"]) <= NUM_BUCKETS + 1
    assert sum(summary["buckets"].values()) == 40
    assert vc.metrics.counters["engine_dispatches"] == 42


def test_convergence_step_and_cut_counters():
    vc = _cluster()
    vc.crash([3])
    rounds, decided, _, _ = vc.run_to_decision(max_steps=32)
    assert decided
    assert vc.metrics.counters["engine_convergence_steps"] == rounds
    assert vc.metrics.counters["engine_cuts_committed"] == 1
    vc2 = _cluster(n=24)
    vc2.crash([1, 2])
    rounds2, cuts2, resolved, _ = vc2.run_until_membership(22, min_cuts=1)
    assert resolved
    assert vc2.metrics.counters["engine_convergence_steps"] == rounds2
    assert vc2.metrics.counters["engine_cuts_committed"] == cuts2


def test_transfer_byte_accounting():
    vc = _cluster()
    # Initial state upload was charged at construction: 4 arrays of (k, n)
    # u32 keys + 2 of (n,) u32 ids + the (n,) alive mask.
    base_h2d = vc.metrics.counters["engine_h2d_bytes"]
    assert base_h2d >= 3 * 16 * 4 * 2 + 16 * 4 * 2 + 16
    vc.crash([1, 2, 3])
    # a small wave's indices go up at one fixed length (``_slot_index``)
    assert vc.metrics.counters["engine_h2d_bytes"] == base_h2d + SMALL_WAVE_SLOTS * 4
    d2h0 = vc.metrics.counters["engine_d2h_bytes"]
    assert vc.membership_size == 16
    assert vc.metrics.counters["engine_d2h_bytes"] == d2h0 + 4
    mask = vc.alive_mask
    assert vc.metrics.counters["engine_d2h_bytes"] == d2h0 + 4 + mask.nbytes


def test_join_wave_accounting_charges_indices_not_device_masks():
    # The join wave's fired-edge mask is DERIVED ON DEVICE (pred >= 0):
    # charging it would require materializing it on host — a blocking
    # fetch on the bootstrap timed path. Only the uploaded slot
    # indices (and the [j] admissibility fetch) are real transfers.
    vc = VirtualCluster.create(
        16, n_slots=20, k=3, h=3, l=1, cohorts=2, fd_threshold=2, seed=0
    )
    vc.assign_cohorts_roundrobin()
    h2d0 = vc.metrics.counters["engine_h2d_bytes"]
    d2h0 = vc.metrics.counters["engine_d2h_bytes"]
    vc.inject_join_wave([16, 17])
    assert vc.metrics.counters["engine_h2d_bytes"] == h2d0 + 2 * 4  # idx only
    assert vc.metrics.counters["engine_d2h_bytes"] == d2h0 + 2  # [j] bools
    # A graceful leave's mask IS host-originated (np.ones): charged.
    h2d1 = vc.metrics.counters["engine_h2d_bytes"]
    vc.initiate_leave([2])
    assert vc.metrics.counters["engine_h2d_bytes"] == h2d1 + 4 + 1 * 3  # idx + [1,k] mask


def test_device_memory_snapshot_sees_live_state():
    vc = _cluster()
    vc.sync()
    memory = engine_telemetry.device_memory_snapshot()
    # The engine state alone holds dozens of live device buffers.
    assert memory["live_buffers"] >= 10
    assert memory["live_buffer_bytes"] > 0
    # Allocator stats are platform-optional (None on CPU) but the keys are
    # always present — the scrape shape is stable across platforms.
    assert "device_bytes_in_use" in memory and "device_peak_bytes" in memory


def test_compiled_memory_analysis_of_engine_step():
    from rapid_tpu.models.state import FaultInputs
    from rapid_tpu.models.virtual_cluster import engine_step_nodonate

    vc = _cluster()
    lowered = engine_step_nodonate.lower(
        vc.cfg, vc.state, FaultInputs.none(vc.cfg)
    )
    analysis = engine_telemetry.compiled_memory_analysis(lowered.compile())
    if analysis is not None:  # backend-optional, shape pinned when present
        assert set(analysis) == {
            "argument_bytes", "output_bytes", "temp_bytes",
            "generated_code_bytes",
        }
        assert analysis["argument_bytes"] > 0
    # A backend object without memory_analysis degrades to None, never raises.
    assert engine_telemetry.compiled_memory_analysis(object()) is None


def test_install_is_idempotent():
    first = engine_telemetry.install()
    assert engine_telemetry.install() is first


# ---------------------------------------------------------------------------
# clustertop: the engine pane
# ---------------------------------------------------------------------------


def test_clustertop_renders_engine_pane():
    vc = _cluster()
    vc.crash([3])
    vc.run_to_decision(max_steps=32)
    host_snapshot = {
        "node": "10.0.0.1:9001", "configuration_id": 7, "membership_size": 3,
        "health": "stable", "metrics": {"view_changes": 1},
        "transport": {}, "recorder": None,
    }
    frame = clustertop.render_frame([host_snapshot, vc.telemetry_snapshot()])
    assert "ENGINE" in frame and "virtual-cluster/16" in frame
    assert "COMPILES" in frame and "DISP99" in frame
    # The host node renders in the node table, not the engine pane.
    assert frame.index("10.0.0.1:9001") < frame.index("ENGINE")


def test_clustertop_tolerates_pre_ledger_engine_snapshots():
    # Snapshots written by pre-ledger code: no "engine" key at all, or a
    # bare/partial section — dashes and omissions, never a crash.
    legacy = {
        "node": "virtual-cluster/64", "configuration_id": 1,
        "membership_size": 64, "health": "stable",
        "metrics": {}, "transport": {}, "recorder": None,
    }
    frame = clustertop.render_frame([legacy])
    assert "ENGINE" not in frame  # no engine data -> no pane
    partial = dict(legacy)
    partial["engine"] = {"compile": {}, "memory": None}
    frame = clustertop.render_frame([partial])
    assert "ENGINE" in frame
    row = _engine_pane_row(frame, "virtual-cluster/64")
    assert "-" in row


def _engine_pane_row(frame: str, node: str) -> str:
    """The node's row INSIDE the engine pane (the node table above also
    carries the node name)."""
    lines = frame.splitlines()
    start = next(i for i, line in enumerate(lines) if line.startswith("ENGINE"))
    return next(line for line in lines[start:] if line.startswith(node))


def test_engine_pane_cache_hit_rate_and_memory_formatting():
    snapshot = {
        "node": "virtual-cluster/1000", "configuration_id": 1,
        "membership_size": 1000, "health": "stable",
        "metrics": {
            "engine_dispatches": 12,
            "engine_h2d_bytes": 3 << 20,
            "engine_d2h_bytes": 2048,
            "engine_dispatch_ms": {
                "run_to_decision": _hist_summary(5.0, 7.0, 100.0),
            },
        },
        "engine": {
            "compile": {"compiles": 9, "persistent_cache_hits": 3,
                        "persistent_cache_misses": 1},
            "memory": {"live_buffer_bytes": 5 << 30,
                       "device_bytes_in_use": 1 << 30},
        },
        "transport": {}, "recorder": None,
    }
    frame = clustertop.render_frame([snapshot])
    row = _engine_pane_row(frame, "virtual-cluster/1000")
    assert "75%" in row  # 3 hits / 4 lookups
    assert "3.0M" in row and "2.0K" in row
    assert "5.00G" in row and "1.00G" in row
    merged = LogHistogram()
    for v in (5.0, 7.0, 100.0):
        merged.observe(v)
    assert f"{merged.quantile(0.99):.1f}" in row


def _hist_summary(*values_ms):
    hist = LogHistogram()
    for value in values_ms:
        hist.observe(value)
    return hist.summary()


# ---------------------------------------------------------------------------
# Tenant-fleet tier (rapid_tpu/tenancy): per-tenant dispatch accounting
# ---------------------------------------------------------------------------

#: The fleet scrape's complete metric-name vocabulary — the single-cluster
#: golden list plus the tenancy tier (tenant counters zero-filled, tenant
#: count + per-dispatch throughput gauges) minus the per-cluster
#: configuration-id gauge (a fleet has B configuration chains, observed via
#: TenantFleet.config_ids()). Same API rule: renaming one breaks scrape
#: configs.
GOLDEN_FLEET_METRIC_NAMES = sorted(
    set(GOLDEN_ENGINE_METRIC_NAMES)
    - {"rapid_configuration_id"}
    | {
        "rapid_engine_tenant_cuts_total",
        "rapid_engine_tenant_rounds_total",
        # Rounds in which the gated step's view change ran (ISSUE 26).
        "rapid_engine_fleet_commit_rounds_total",
        # Rounds in which the round's own gated arms ran for the fleet
        # (ISSUE 30).
        "rapid_engine_fleet_invalidation_rounds_total",
        "rapid_engine_fleet_classic_rounds_total",
        # Lockstep rounds the whole-wave loops ran (ISSUE 33).
        "rapid_engine_fleet_wave_rounds_total",
        "rapid_engine_tenant_rounds_per_dispatch",
        "rapid_engine_tenants",
        # Quarantine census (ISSUE 15): the zero-filled cumulative counter
        # and the current-census gauge are part of every fleet scrape from
        # the first snapshot — a quarantine must never mint a new series.
        "rapid_engine_tenant_quarantines_total",
        "rapid_engine_tenants_quarantined",
    }
)


def _fleet(b=4):
    from rapid_tpu.tenancy import TenantFleet

    fleet = TenantFleet.create(
        b, 12, n_slots=16, k=3, cohorts=2, knobs=[(3, 1, 2)] * b
    )
    fleet.faults = fleet.faults._replace(
        crashed=fleet.faults.crashed.at[:, 3].set(True)
    )
    return fleet


def test_fleet_prometheus_names_are_golden():
    fleet = _fleet()
    fleet.step()
    fleet.run_to_decision(max_steps=32)
    names = exposition.metric_names(fleet.prometheus_text())
    assert names == GOLDEN_FLEET_METRIC_NAMES


def test_fleet_dispatch_histogram_carries_fleet_step_phase():
    # Satellite (ISSUE 10): engine_dispatch_ms gains the fleet phase labels
    # — per-tenant dispatch accounting rides the same bounded instrument,
    # keyed fleet_step / fleet_decision / fleet_wave.
    fleet = _fleet()
    for _ in range(5):
        fleet.step()
    fleet.run_to_decision(max_steps=8)
    fleet.run_until_membership(fleet.membership_sizes(), max_steps=8)
    family = fleet.metrics.phase_timings["engine_dispatch"]
    assert set(family) == {"fleet_step", "fleet_decision", "fleet_wave"}
    assert isinstance(family["fleet_step"], LogHistogram)
    assert family["fleet_step"].count == 5
    assert fleet.metrics.counters["engine_dispatches"] == 7


def test_fleet_snapshot_tenancy_section():
    fleet = _fleet()
    fleet.step()  # 4 tenants, 1 round each, one dispatch
    rounds, decided, _, _ = fleet.run_to_decision(max_steps=32)
    snap = fleet.telemetry_snapshot()
    tenancy = snap["engine"]["tenancy"]
    assert tenancy["tenants"] == 4
    assert tenancy["tenant_rounds_total"] == 4 + int(rounds.sum())
    assert tenancy["tenant_cuts_total"] == int(decided.sum()) == 4
    # Per-dispatch tenant throughput: tenant-rounds over dispatches.
    assert tenancy["tenant_rounds_per_dispatch"] == round(
        tenancy["tenant_rounds_total"] / 2, 3
    )
    json.dumps(snap)  # the --metrics-dump / clustertop artifact


def test_clustertop_engine_pane_shows_tenants():
    fleet = _fleet()
    fleet.step()
    vc = _cluster()
    vc.run_to_decision(max_steps=8)
    frame = clustertop.render_frame(
        [vc.telemetry_snapshot(), fleet.telemetry_snapshot()]
    )
    assert "TENANTS" in frame
    fleet_row = _engine_pane_row(frame, "tenant-fleet/4x16")
    assert fleet_row.split()[1] == "4"
    # A single-cluster snapshot dashes the column, never crashes.
    vc_row = _engine_pane_row(frame, "virtual-cluster/16")
    assert vc_row.split()[1] == "-"


# ---------------------------------------------------------------------------
# Streaming tier (rapid_tpu/serving): the stream section's golden names
# ---------------------------------------------------------------------------

#: The streaming scrape's complete metric-name vocabulary — the
#: single-cluster golden list plus the stream tier: the pipeline gauges
#: (rates NaN pre-drain so the series set is stable from the first scrape),
#: the zero-filled wave/cut counters, and the alert->commit latency
#: histogram. Same API rule: renaming one breaks scrape configs.
GOLDEN_STREAM_METRIC_NAMES = sorted(
    set(GOLDEN_ENGINE_METRIC_NAMES)
    | {
        "rapid_engine_stream_alert_to_commit_ms_bucket",
        "rapid_engine_stream_alert_to_commit_ms_count",
        "rapid_engine_stream_alert_to_commit_ms_sum",
        "rapid_engine_stream_cuts_total",
        "rapid_engine_stream_depth",
        "rapid_engine_stream_overlap_efficiency",
        "rapid_engine_stream_p99_alert_to_commit_ms",
        "rapid_engine_stream_rounds_per_wave",
        "rapid_engine_stream_view_changes_per_sec",
        "rapid_engine_stream_waves_completed",
        "rapid_engine_stream_waves_in_flight",
        "rapid_engine_stream_waves_submitted",
        "rapid_engine_stream_waves_total",
    }
)


def _streamed_cluster():
    from rapid_tpu.serving import PoissonChurn, StreamDriver

    vc = VirtualCluster.create(
        24, n_slots=32, k=3, h=3, l=1, cohorts=2, fd_threshold=2, seed=0
    )
    vc.assign_cohorts_roundrobin()
    driver = StreamDriver(vc, rounds_per_wave=2, depth=2)
    for wave in PoissonChurn(24, 32, rate=1.0, seed=4).waves(3):
        driver.submit(wave)
    driver.drain()
    return vc


def test_stream_prometheus_names_are_golden():
    vc = _streamed_cluster()
    names = exposition.metric_names(vc.prometheus_text())
    assert names == GOLDEN_STREAM_METRIC_NAMES


def test_stream_section_only_grows_series_when_attached():
    # A batch-only driver keeps the batch vocabulary — attaching a
    # StreamDriver is what opts a scrape into the stream tier.
    vc = _cluster()
    vc.step()
    names = exposition.metric_names(vc.prometheus_text())
    assert not any("stream" in name for name in names)
    assert names == GOLDEN_ENGINE_METRIC_NAMES


def test_stream_vocabulary_complete_from_attach_not_first_completion():
    # The alert->commit timer is minted lazily on the first wave
    # COMPLETION; the scrape must still carry the full stream vocabulary —
    # histogram triplet included, zero-count — from the moment the driver
    # attaches, or dashboards keyed on the golden names see the series set
    # change mid-run (the stable-series rule the counters follow).
    from rapid_tpu.serving import StreamDriver

    vc = _cluster()
    StreamDriver(vc, rounds_per_wave=2, depth=2)  # attach, zero traffic
    names = exposition.metric_names(vc.prometheus_text())
    assert names == GOLDEN_STREAM_METRIC_NAMES


def test_a_partitioned_clusters_scrape_grows_the_consensus_path_and_nothing_else():
    """ISSUE 43: the three counters of the consensus path are minted by a
    cluster's first ``set_partition``, at 0, and are in every scrape of it
    from then on; a cluster that never sets one keeps the golden list."""
    assert exposition.CONSENSUS_PATH_COUNTERS == (
        "engine_classic_rounds", "engine_classic_decisions", "engine_fast_decisions")
    vc = _cluster()
    vc.sync()  # a first dispatch, so the phase histogram's series exist
    assert exposition.metric_names(vc.prometheus_text()) == GOLDEN_ENGINE_METRIC_NAMES
    vc.set_partition([0], [1, 2])
    names = exposition.metric_names(vc.prometheus_text())
    assert names == sorted(set(GOLDEN_ENGINE_METRIC_NAMES) | {
        f"rapid_{name}_total" for name in exposition.CONSENSUS_PATH_COUNTERS})
    assert vc.metrics.phase_timings["engine_dispatch"]["inject_partition"].count == 1
    vc.set_partition([], [])  # healing clears the lane, never the series
    assert exposition.metric_names(vc.prometheus_text()) == names


def test_dispatch_phase_vocabulary_enforced_at_write_time():
    # Satellite (ISSUE 11): the phase vocabulary is enforced where it is
    # WRITTEN — a typo'd phase raises instead of silently minting a new
    # histogram series that every dashboard keyed on the known names would
    # miss.
    from rapid_tpu.utils.dispatch import ENGINE_DISPATCH_PHASES

    assert {"stream_enqueue", "stream_fetch"} <= ENGINE_DISPATCH_PHASES
    vc = _cluster()
    with pytest.raises(ValueError, match="unregistered engine dispatch phase"):
        with vc._dispatch("stream_enque"):  # the typo class under test
            pass
    # The registered pair lands in the shared family like every entrypoint.
    vc.stream_step()
    family = vc.metrics.phase_timings["engine_dispatch"]
    assert family["stream_enqueue"].count == 1


def test_clustertop_renders_stream_pane():
    vc = _streamed_cluster()
    frame = clustertop.render_frame([vc.telemetry_snapshot()])
    assert "STREAM" in frame and "OVERLAP" in frame and "INFLIGHT" in frame
    lines = frame.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("STREAM"))
    row = next(l for l in lines[start:] if l.startswith("virtual-cluster/"))
    cells = row.split()
    assert cells[1] == "0"  # nothing in flight after drain
    assert cells[2] == "3" and cells[3] == "3"  # submitted == completed


def test_clustertop_stream_pane_tolerates_pre_stream_snapshots():
    # Batch-only snapshots (no stream section) render no stream pane; a
    # pre-drain stream section (None rates) renders dashes, never a crash.
    vc = _cluster()
    frame = clustertop.render_frame([vc.telemetry_snapshot()])
    assert "INFLIGHT" not in frame
    pre_drain = {
        "node": "virtual-cluster/64", "metrics": {}, "transport": {},
        "recorder": None,
        "engine": {"stream": {
            "waves_submitted": 2, "waves_completed": 0, "waves_in_flight": 2,
            "view_changes_per_sec": None, "overlap_efficiency": None,
            "p99_alert_to_commit_ms": None,
        }},
    }
    frame = clustertop.render_frame([pre_drain])
    assert "INFLIGHT" in frame
    lines = frame.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("STREAM"))
    row = next(l for l in lines[start:] if l.startswith("virtual-cluster/64"))
    assert "-" in row  # the undrained rates dash


def test_engine_counters_zero_filled_only_for_engine_snapshots():
    # A host snapshot must NOT grow engine series; an engine snapshot
    # exposes them even before the first dispatch.
    host = {"node": "h", "metrics": {}, "transport": {}, "recorder": None}
    host_names = exposition.metric_names(exposition.prometheus_text(host))
    assert not any("engine" in name for name in host_names)
    vc = _cluster()  # no dispatch at all yet
    names = exposition.metric_names(vc.prometheus_text())
    assert "rapid_engine_dispatches_total" in names
    assert "rapid_engine_steps_total" in names


# ---------------------------------------------------------------------------
# Device telemetry plane (rapid_tpu/models/state.TelemetryLanes): the
# activity section's golden names
# ---------------------------------------------------------------------------

#: The device-telemetry-plane vocabulary a ``telemetry=1`` scrape adds: the
#: per-round activity counters, the derived rate/peak gauges, the
#: fast/classic decision-path split, and the rounds-undecided log2
#: histogram. Present exactly when the driver carries the lanes; a
#: telemetry=0 scrape's name set is unchanged (the stable-series rule).
#: Same API rule as every golden list here: renaming one breaks scrape
#: configs.
GOLDEN_ACTIVITY_METRIC_NAMES = [
    "rapid_engine_activity_active_fraction",
    "rapid_engine_activity_active_peak",
    "rapid_engine_activity_active_sum_total",
    "rapid_engine_activity_alerts_total",
    "rapid_engine_activity_conflict_rate",
    "rapid_engine_activity_conflict_rounds_total",
    "rapid_engine_activity_dissent_total",
    "rapid_engine_activity_fast_path_share",
    "rapid_engine_activity_invalidation_dense_rounds_total",
    "rapid_engine_activity_invalidation_rounds_total",
    "rapid_engine_activity_invalidations_total",
    "rapid_engine_activity_peak_active_fraction",
    "rapid_engine_activity_proposals_total",
    "rapid_engine_activity_rounds_total",
    "rapid_engine_activity_rounds_undecided_total",
    "rapid_engine_activity_tally_sum_total",
    "rapid_engine_activity_view_change_dense_total",
    "rapid_engine_activity_winning_tally_mean",
    "rapid_engine_decision_path_total",
]


def _telemetry_cluster():
    vc = VirtualCluster.create(
        16, k=3, h=3, l=1, cohorts=2, fd_threshold=2, seed=0, telemetry=True
    )
    vc.assign_cohorts_roundrobin()
    return vc


def test_activity_names_golden_and_zero_filled_from_attach():
    # The full activity vocabulary exists before any sync boundary (the
    # host-side cache is zero-minted at attach), every sample at 0 — one
    # step only mints the shared dispatch histogram, never an activity
    # value: the scrape reads the cache, not the device lanes.
    vc = _telemetry_cluster()
    vc.step()
    text = vc.prometheus_text()
    names = exposition.metric_names(text)
    assert names == sorted(
        set(GOLDEN_ENGINE_METRIC_NAMES) | set(GOLDEN_ACTIVITY_METRIC_NAMES)
    )
    activity_samples = [
        line for line in text.splitlines()
        if line.startswith(("rapid_engine_activity", "rapid_engine_decision"))
    ]
    assert activity_samples
    assert all(line.split()[-1] in ("0", "0.0") for line in activity_samples)
    # And a telemetry=0 scrape is untouched — no activity names, ever
    # (pinned against the same golden list the pre-telemetry engine used).
    plain = _cluster()
    plain.step()
    assert exposition.metric_names(
        plain.prometheus_text()
    ) == GOLDEN_ENGINE_METRIC_NAMES


def test_activity_series_measure_after_the_sync_boundary():
    vc = _telemetry_cluster()
    vc.crash([3])
    vc.run_to_decision(max_steps=32)
    # The scrape reads the HOST cache: still zero until a sync boundary.
    before = vc.prometheus_text()
    assert 'rapid_engine_decision_path_total{node="virtual-cluster/16",' \
        'path="fast"} 0' in before
    vc.sync()
    text = vc.prometheus_text()
    assert 'path="fast"} 1' in text
    assert 'path="classic"} 0' in text
    rounds_line = next(
        line for line in text.splitlines()
        if line.startswith("rapid_engine_activity_rounds_total")
    )
    assert int(rounds_line.split()[-1]) > 0


def test_the_invalidation_lanes_count_the_rounds_a_tenant_needed_the_arm():
    # The fleet carries its own count of the rounds `invalidation` ran for
    # SOME tenant (GATE_ROUND_COUNTERS, the pass's `invalidation_ran`); the
    # lanes add up, in every tenant's plane, the rounds THAT tenant needed
    # it, and of those the ones that took the dense loop: none here, the
    # bucket of 128 holds all 32 slots.
    import numpy as np

    from rapid_tpu.tenancy import TenantFleet

    fleet = TenantFleet.create(
        3, 28, n_slots=32, k=10, cohorts=2, knobs=[(9, 4, 2)] * 3,
        delivery_spread=3, telemetry=True,
    )
    crashed = np.zeros((3, 32), dtype=bool)
    crashed[0, [3, 9, 17]] = crashed[1, [5]] = True  # tenant 2 stays quiet
    fleet.faults = fleet.faults._replace(crashed=jnp.asarray(crashed))
    for _ in range(12):
        fleet.step()
    fleet.sync()
    ran = fleet.metrics.counters["engine_fleet_invalidation_rounds"]
    own = [activity["invalidation_rounds"] for activity in fleet.tenant_activity]
    assert 0 < max(own) <= ran <= sum(own) < 24 and own[2] == 0
    for activity in fleet.tenant_activity:
        assert activity["rounds"] == 12
        assert activity["invalidation_dense_rounds"] == 0
    digest = np.asarray(fleet_digest(fleet))
    fields = engine_telemetry.TELEMETRY_DIGEST_FIELDS
    assert fields[-3:] == (
        "invalidation_rounds", "invalidation_dense_rounds", "view_change_dense",
    )
    assert digest[:, len(fields) - 3].tolist() == own
    assert digest[:, len(fields) - 2].tolist() == [0] * 3
    # ... and every commit flipped its cut's own ring positions: no tenant's
    # cut (3 and 1 of 32 slots) overflowed the view change's bucket of 128.
    assert digest[:, len(fields) - 1].tolist() == [0] * 3
    assert sum(int(a["decisions_fast"]) for a in fleet.tenant_activity) == 2
    text = fleet.prometheus_text()
    assert (
        'rapid_engine_activity_invalidation_rounds_total'
        f'{{node="tenant-fleet/3x32",tenant="1"}} {own[1]}'
    ) in text
    assert (
        'rapid_engine_activity_invalidation_dense_rounds_total'
        '{node="tenant-fleet/3x32"} 0'
    ) in text


def fleet_digest(fleet):
    from rapid_tpu.tenancy.fleet import fleet_telemetry_digest

    return fleet_telemetry_digest(fleet.telem)


@pytest.mark.parametrize("observed", [False, True])
def test_only_an_observed_round_reads_the_dense_flag(observed):
    # The lanes are written where a TelemetryLanes pytree rides along and
    # nowhere else: in the observers-off step the `invalidation`
    # conditional's second output, whether the arm ran dense, flows into no
    # output of the program (dead code the compiler drops); with the lanes
    # riding it is added to one.
    from rapid_tpu.models import virtual_cluster as vcm

    vc = VirtualCluster.create(
        28, n_slots=32, k=3, h=3, l=1, cohorts=2, fd_threshold=2, telemetry=observed
    )
    carried = (vc.state, vc.telem) if observed else (vc.state,)
    masks = jax.eval_shape(vcm.edge_masks_build, vc.cfg, vc.state, vc.faults)
    jaxpr = vcm._ROUND_PROGRAMS["step"][int(observed)].trace(
        vc.cfg, *carried, vc.faults, masks
    ).jaxpr.jaxpr
    (arm,) = [  # the round's only conditional traced under `cut_detection`
        eqn for eqn in jaxpr.eqns
        if eqn.primitive.name == "cond"
        and str(eqn.source_info.name_stack) == "cut_detection"
    ]
    bits, ran_dense = arm.outvars
    assert ran_dense.aval.shape == () and ran_dense.aval.dtype == bool
    assert len(arm.params["branches"]) == 2
    tainted = {id(ran_dense)}  # what the flag flows into, in program order
    for eqn in jaxpr.eqns:
        if any(id(var) in tainted for var in eqn.invars):
            tainted.update(id(var) for var in eqn.outvars)
    reaches_an_output = any(id(var) in tainted for var in jaxpr.outvars)
    assert reaches_an_output == observed
    assert any(any(var is bits for var in eqn.invars) for eqn in jaxpr.eqns)


def test_fleet_activity_carries_per_tenant_labels():
    from rapid_tpu.tenancy import TenantFleet

    fleet = TenantFleet.create(
        4, 12, n_slots=16, k=3, cohorts=2, knobs=[(3, 1, 2)] * 4,
        telemetry=True,
    )
    fleet.faults = fleet.faults._replace(
        crashed=fleet.faults.crashed.at[:, 3].set(True)
    )
    fleet.run_to_decision(max_steps=32)
    fleet.sync()
    text = fleet.prometheus_text()
    names = exposition.metric_names(text)
    assert names == sorted(
        set(GOLDEN_FLEET_METRIC_NAMES) | set(GOLDEN_ACTIVITY_METRIC_NAMES)
    )
    # The aggregate renders unlabelled; every tenant gets its own variant.
    for t in range(4):
        assert f'tenant="{t}"' in text
    tenant_fast = [
        line for line in text.splitlines()
        if line.startswith("rapid_engine_decision_path_total")
        and 'path="fast"' in line and "tenant=" in line
    ]
    assert len(tenant_fast) == 4
    assert all(line.split()[-1] == "1" for line in tenant_fast)
