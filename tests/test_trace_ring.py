"""Device round-trace ring (ISSUE 17): the flight recorder INSIDE the engine.

The acceptance bar mirrors tests/test_telemetry_plane.py's, one refinement
deeper: a ``trace=R`` engine must be bit-identical — state, fault pytrees,
cut sequences, config-id chains, decision rounds, AND the telemetry lanes
themselves — to the ``trace=0`` telemetry engine on every driver spelling
(per-step, fused convergence, fleet wave, streaming pipeline). The ring is
write-only observation; perturbing the lanes it refines would be the same
bug as perturbing the protocol.

The ring's own contract (the decode pins ``engine_telemetry.trace_summary``
documents): the ring holds exactly the last ``min(R, total)`` rounds, the
wrap counter reconciles with the cursor AND with the telemetry plane's
``tl_rounds``, and the decode order is monotone across a wrap — the
``(epoch, round)`` stamps of the rotated window are strictly
lexicographically increasing, with contiguous global ``seq`` ordinals.

Budget (the PR-10 convention): every single-cluster test shares one
``trace=32`` program geometry so the jit cache amortizes the compiles; the
wrap test's tiny ``trace=6`` ring and the sharded/fleet/stream programs are
the only extra compile-bearing variants.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from rapid_tpu.models.virtual_cluster import VirtualCluster
from rapid_tpu.serving import PoissonChurn, StreamDriver
from rapid_tpu.tenancy import TenantFleet
from rapid_tpu.utils.engine_telemetry import (
    TRACE_PATH_NAMES,
    TRACE_RECORD_FIELDS,
    first_divergent_round,
    zero_trace_summary,
)

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

#: The shared single-cluster ring capacity (one compile per driver kind).
R = 32


def _cluster(trace, n=24, n_slots=40, seed=0, **kw):
    vc = VirtualCluster.create(
        n, n_slots=n_slots, k=3, h=3, l=1, cohorts=2, fd_threshold=2,
        seed=seed, telemetry=True, trace=trace, **kw,
    )
    vc.assign_cohorts_roundrobin()
    return vc


def _trees_equal(a, b) -> bool:
    return bool(jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b
    )))


def _host(tree):
    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


def _churn_drive(vc, steps=10):
    """The test_telemetry_plane churn drive, verbatim: crash + join through
    the per-step seam, cut labels both sides observe identically."""
    cuts, ids, rounds = [], [], []
    joiners = np.nonzero(~np.asarray(vc.state.alive))[0][:2].tolist()
    vc.crash([3, 5])
    for i in range(steps):
        if i == 4:
            vc.inject_join_wave(joiners)
        was_alive = np.asarray(vc.state.alive)
        events = vc.step()
        if bool(events.decided):
            mask = np.asarray(events.winner_mask)
            cuts.append(frozenset(
                (s, "down" if was_alive[s] else "up")
                for s in np.nonzero(mask)[0].tolist()
            ))
            ids.append(vc.config_id)
            rounds.append(i)
    return cuts, ids, rounds


def _stamps(records):
    return [(r["epoch"], r["round"]) for r in records]


# ---------------------------------------------------------------------------
# Config gate: trace is a refinement of the telemetry plane
# ---------------------------------------------------------------------------


def test_trace_requires_telemetry_and_rejects_negative_capacity():
    with pytest.raises(ValueError, match="requires telemetry"):
        VirtualCluster.create(24, k=3, h=3, l=1, trace=4, telemetry=False)
    with pytest.raises(ValueError, match=">= 0"):
        VirtualCluster.create(24, k=3, h=3, l=1, trace=-1, telemetry=True)


# ---------------------------------------------------------------------------
# Bit-identity: trace=R vs trace=0, every driver spelling
# ---------------------------------------------------------------------------


def test_step_drive_bit_identical_trace_on_off():
    """The tier-1 representative: one crash+join churn drive, trace on vs
    off (both telemetry=1) — identical cuts, config-id chains, decision
    rounds, final state/fault pytrees, AND identical telemetry lanes (the
    ring must not perturb the plane it refines)."""
    off = _cluster(trace=0)
    on = _cluster(trace=R)
    expected = _churn_drive(off)
    got = _churn_drive(on)
    assert expected[0], "drive produced no cuts — the differential is vacuous"
    assert got == expected
    assert _trees_equal(on.state, off.state)
    assert _trees_equal(on.faults, off.faults)
    assert _trees_equal(_host(on.telem), _host(off.telem))
    assert on.config_id == off.config_id

    on.sync()
    trace = on.trace
    assert trace["capacity"] == R
    assert trace["rounds_recorded"] == 10 == on.activity["rounds"]
    assert trace["wraps"] == 0
    assert trace["rounds_held"] == 10
    assert [r["seq"] for r in trace["records"]] == list(range(10))
    assert trace["decisions_held"] == len(expected[0])
    decided = [r for r in trace["records"] if r["path"]]
    # The ring's decision records name the SAME rounds the host drive saw
    # decide, with a registered path code.
    assert [r["seq"] for r in decided] == expected[2]
    assert all(r["path"] in TRACE_PATH_NAMES for r in decided)
    assert off.trace is None  # trace=0: no ring, no fetch, ever


def test_fused_drivers_bit_identical_and_ring_path_independent():
    """``run_to_decision``/``run_until_membership`` with the ring riding
    the while-loop carry: identical resolution to trace=0, and the ring a
    fused drive accumulates equals a per-step drive's ring raw leaf for
    raw leaf (the while-loop body IS the step body)."""
    off = _cluster(trace=0, seed=1)
    on = _cluster(trace=R, seed=1)
    stepped = _cluster(trace=R, seed=1)
    off.crash([2, 7]); on.crash([2, 7]); stepped.crash([2, 7])

    expected = off.run_to_decision(max_steps=32)
    got = on.run_to_decision(max_steps=32)
    assert got[0] == expected[0] and got[1] == expected[1]
    assert got[3] == expected[3]
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(expected[2]))
    assert _trees_equal(on.state, off.state)
    assert _trees_equal(_host(on.telem), _host(off.telem))

    for _ in range(got[0]):
        stepped.step()
    assert _trees_equal(_host(on.trace_ring), _host(stepped.trace_ring))

    # The multi-cut wave: same resolution, same config chain, on vs off.
    off2 = _cluster(trace=0, seed=2)
    on2 = _cluster(trace=R, seed=2)
    for vc in (off2, on2):
        vc.crash([1, 4, 9])
    expected2 = off2.run_until_membership(21, max_steps=64, min_cuts=1)
    got2 = on2.run_until_membership(21, max_steps=64, min_cuts=1)
    assert got2 == expected2
    assert _trees_equal(on2.state, off2.state)
    assert on2.config_id == off2.config_id
    on2.sync()
    assert on2.trace["rounds_recorded"] == on2.activity["rounds"]


# ---------------------------------------------------------------------------
# The ring contract: last min(R, total), wrap reconciliation, monotone decode
# ---------------------------------------------------------------------------


def test_ring_holds_exactly_last_min_R_total_and_decode_is_monotone():
    """The wraparound property, pinned against an unwrapped reference twin:
    a trace=6 ring driven 17 rounds holds exactly the LAST 6 records a
    trace=32 twin of the same drive recorded, field for field; the wrap
    counter reconciles with the cursor (``wraps == cursor // R``) and the
    cursor with the telemetry plane (``cursor == tl_rounds``); the decoded
    ``(epoch, round)`` stamps stay strictly increasing across the wrap."""
    small, big = _cluster(trace=6, seed=3), _cluster(trace=R, seed=3)
    joiners = np.nonzero(~np.asarray(small.state.alive))[0][:2].tolist()

    # Pre-wrap boundary: the ring is just the prefix.
    for vc in (small, big):
        vc.crash([3, 5])
        for _ in range(4):
            vc.step()
        vc.sync()
    pre = small.trace
    assert (pre["rounds_recorded"], pre["rounds_held"], pre["wraps"]) == (4, 4, 0)
    assert [r["seq"] for r in pre["records"]] == [0, 1, 2, 3]
    assert pre["records"] == big.trace["records"]

    # Drive past two wraps (17 records through a 6-slot ring).
    for vc in (small, big):
        vc.inject_join_wave(joiners)
        for _ in range(13):
            vc.step()
        vc.sync()
    trace, ref = small.trace, big.trace
    total = 17
    assert trace["rounds_recorded"] == total == small.activity["rounds"]
    assert trace["rounds_held"] == min(6, total) == 6
    assert trace["wraps"] == total // 6 == 2
    # Exactly the last 6 rounds ever recorded, bit for bit — nothing
    # phantom, nothing stale from before the wrap.
    assert trace["records"] == ref["records"][-6:]
    assert [r["seq"] for r in trace["records"]] == list(range(total - 6, total))
    stamps = _stamps(trace["records"])
    assert stamps == sorted(stamps) and len(set(stamps)) == len(stamps)
    # The unwrapped twin held everything and agrees on the reconciliation.
    assert ref["rounds_held"] == total and ref["wraps"] == 0
    ref_stamps = _stamps(ref["records"])
    assert ref_stamps == sorted(ref_stamps) and len(set(ref_stamps)) == total
    # Two decodes of overlapping windows of the SAME history never fork.
    assert first_divergent_round(trace, ref) is None


def test_zero_minted_attach_reads_an_empty_ring():
    """The never-mint-a-series-mid-run rule: a fresh trace=R engine reads a
    fully-formed all-zero summary (capacity, no records) BEFORE any sync —
    and its telemetry snapshot carries the section from the first frame."""
    vc = _cluster(trace=R, seed=4)
    assert vc.trace == zero_trace_summary(R)
    assert vc.trace["capacity"] == R and vc.trace["records"] == []
    snap = vc.telemetry_snapshot()
    assert snap["engine"]["trace"]["rounds_recorded"] == 0
    # The accessor copies: mutating a read never corrupts the cache.
    vc.trace["records"].append("garbage")
    assert vc.trace["records"] == []


# ---------------------------------------------------------------------------
# Fleet: tenant rings coast-gate exactly like the lanes they refine
# ---------------------------------------------------------------------------


def _fleet(trace, b=3, n=16, seed0=10):
    clusters = []
    for i in range(b):
        vc = VirtualCluster.create(
            n, k=3, h=3, l=1, cohorts=2, fd_threshold=2, seed=seed0 + i,
            telemetry=True, trace=trace,
        )
        vc.assign_cohorts_roundrobin()
        vc.crash(list(range(1, 2 + i)))  # tenants resolve at different rounds
        clusters.append(vc)
    return clusters


def test_fleet_wave_rings_bit_identical_to_per_cluster_drives():
    """The coast-gating pin at ring grain: tenants resolving at different
    rounds coast with FROZEN rings — each tenant's ring equals its own
    per-cluster drive's ring, record for record — and the traced wave's
    results match the trace=0 wave."""
    singles = _fleet(trace=R)
    targets = [vc.membership_size - (1 + i) for i, vc in enumerate(singles)]
    expected = [
        vc.run_until_membership(t, max_steps=64, min_cuts=1)
        for vc, t in zip(singles, targets)
    ]
    assert all(r[2] for r in expected), "a tenant failed to resolve"

    fleet = TenantFleet.from_clusters(_fleet(trace=R))
    rounds, cuts, resolved, _ = fleet.run_until_membership(
        np.asarray(targets), max_steps=64, min_cuts=1
    )
    assert resolved.all()
    assert rounds.tolist() == [r[0] for r in expected]
    assert cuts.tolist() == [r[1] for r in expected]
    fleet.sync()
    tenant_trace = fleet.tenant_trace
    for t, vc in enumerate(singles):
        tenant_ring = jax.tree_util.tree_map(
            lambda x, t=t: np.asarray(x)[t], fleet.trace_ring
        )
        assert _trees_equal(tenant_ring, _host(vc.trace_ring)), t
        vc.sync()
        assert tenant_trace[t] == vc.trace, t

    # Same wave, trace off: the fleet results are unchanged.
    off = TenantFleet.from_clusters(_fleet(trace=0))
    rounds0, cuts0, resolved0, _ = off.run_until_membership(
        np.asarray(targets), max_steps=64, min_cuts=1
    )
    assert resolved0.all()
    assert rounds0.tolist() == rounds.tolist()
    assert cuts0.tolist() == cuts.tolist()
    assert _trees_equal(off.state, fleet.state)
    assert _trees_equal(_host(off.telem), _host(fleet.telem))
    assert off.tenant_trace is None


# ---------------------------------------------------------------------------
# Stream: the drain boundary decodes the ring and attributes waves
# ---------------------------------------------------------------------------


def test_stream_drive_bit_identical_and_drain_attributes_waves():
    """The pipelined driver over a traced target: bit-identical cuts/state
    to the trace=0 stream, zero extra fetches before the drain, and the
    drain's ring decomposition attributes every submitted wave (none
    evicted at this depth) with decision offsets inside the wave span."""
    waves = PoissonChurn(24, 40, rate=1.0, seed=7).waves(6)

    on = _cluster(trace=R, seed=0)
    driver_on = StreamDriver(on, rounds_per_wave=4, depth=2)
    for wave in waves:
        driver_on.submit(wave)
    result_on = driver_on.drain()

    off = _cluster(trace=0, seed=0)
    driver_off = StreamDriver(off, rounds_per_wave=4, depth=2)
    for wave in waves:
        driver_off.submit(wave)
    result_off = driver_off.drain()

    assert result_on.cuts == result_off.cuts
    assert result_on.waves == result_off.waves == 6
    assert _trees_equal(on.state, off.state)
    assert _trees_equal(on.faults, off.faults)
    assert on.config_id == off.config_id

    assert on.trace["rounds_recorded"] == result_on.rounds == 24
    tj = driver_on.last_trajectory
    assert tj is not None
    assert driver_off.last_trajectory is None  # trace=0: no ring to decompose
    assert tj["rounds_per_wave"] == 4
    assert tj["waves_attributed"] + tj["waves_evicted"] == 6
    assert tj["waves_evicted"] == 0  # R=32 holds all 24 streamed rounds
    assert tj["decided_waves"] + tj["undecided_waves"] == 6
    assert tj["decided_waves"] >= 1
    assert 1 <= tj["rounds_to_decision_p50"] <= 4
    assert 1 <= tj["rounds_to_decision_max"] <= 4


# ---------------------------------------------------------------------------
# Sharded: the mesh twin and the fleet placement rules
# ---------------------------------------------------------------------------


def test_sharded_step_trace_bit_identical_and_fleet_rings_shard():
    """The ring under a real device mesh: a ``VirtualCluster`` built on the
    mesh steps ``sharded_program("step", ..., carried=3)`` and matches the
    single-device per-step drive bit for bit — state, lanes, AND ring — and
    tenant-stacked rings place onto the 3-D fleet mesh through the same
    rule table (``fleet_trace_shardings``: leading 'tenant' axis, lane dims
    replicated, values unchanged)."""
    from rapid_tpu.parallel.mesh import (
        TENANT_AXIS,
        fleet_trace_shardings,
        make_mesh,
        off_table,
        shard_pytree,
    )

    single = _cluster(trace=R, seed=6)
    single.crash([2, 7])
    for _ in range(8):
        single.step()

    mesh = make_mesh(jax.devices()[:8])
    vc = _cluster(trace=R, seed=6, mesh=mesh)
    vc.crash([2, 7])
    for _ in range(8):
        vc.step()
    assert _trees_equal(vc.state, single.state)
    # (the mesh's view change gathers ``ring_alive`` whole at its one commit
    # and counts it; the one-device step flipped the cut's own positions)
    assert (int(vc.telem.tl_view_change_dense), int(single.telem.tl_view_change_dense)) == (1, 0)
    assert _trees_equal(
        _host(vc.telem._replace(tl_view_change_dense=single.telem.tl_view_change_dense)),
        _host(single.telem),
    )
    assert _trees_equal(_host(vc.trace_ring), _host(single.trace_ring))
    assert off_table(vc.trace_ring, mesh) == () and off_table(vc.telem, mesh) == ()
    single.sync()
    vc.sync()
    assert single.trace["rounds_recorded"] == vc.trace["rounds_recorded"] == 8

    fleet = TenantFleet.from_clusters(_fleet(trace=R, b=4))
    shardings = fleet_trace_shardings(
        make_mesh(jax.devices()[:8], shape=(2, 2, 2))
    )
    for leaf in jax.tree_util.tree_leaves(shardings):
        assert leaf.spec and leaf.spec[0] == TENANT_AXIS
    placed = shard_pytree(
        fleet.trace_ring, shardings,
        mesh=make_mesh(jax.devices()[:8], shape=(2, 2, 2)),
    )
    assert _trees_equal(_host(placed), _host(fleet.trace_ring))


# ---------------------------------------------------------------------------
# Host decode instruments: divergence naming, timeline merge, dashboard pane
# ---------------------------------------------------------------------------


def test_first_divergent_round_names_the_first_forked_record():
    vc = _cluster(trace=R, seed=8)
    vc.crash([2, 7])
    vc.run_to_decision(max_steps=32)
    vc.sync()
    a = vc.trace
    assert a["records"], "drive recorded nothing — the fork test is vacuous"
    assert first_divergent_round(a, a) is None

    # A tampered field forks at exactly that record's global ordinal.
    b = dict(a)
    b["records"] = [dict(r) for r in a["records"]]
    victim = len(b["records"]) // 2
    b["records"][victim]["active"] += 1
    assert first_divergent_round(a, b) == a["records"][victim]["seq"]

    # A truncated history forks at the first round the shorter run never
    # executed, even where the overlapping records agree.
    c = dict(a)
    c["records"] = [dict(r) for r in a["records"][:-1]]
    c["rounds_recorded"] = a["rounds_recorded"] - 1
    assert first_divergent_round(a, c) == c["rounds_recorded"]


def test_traceview_merges_the_engine_lane_from_a_trace_artifact(tmp_path):
    """The flight-recorder join: a repro directory's ``trace.json`` becomes
    the synthetic ``(engine)`` lane — one registered ``engine_round`` event
    per held record, decisions and conflicts interleaved — through THE
    shared loader (``scenario_snapshots``), ordered by global ``seq``."""
    import traceview

    vc = _cluster(trace=R, seed=9)
    vc.crash([2, 7])
    vc.run_to_decision(max_steps=32)
    vc.sync()
    summary = vc.trace
    (tmp_path / "trace.json").write_text(json.dumps(summary))
    (tmp_path / "schedule.json").write_text("{}")  # metadata, never a snapshot

    snapshots = traceview.scenario_snapshots(tmp_path)
    assert [s["node"] for s in snapshots] == [traceview.ENGINE_LANE]
    events = traceview.merge_events(snapshots)
    rounds = [e for e in events if e["name"] == "engine_round"]
    assert [e["fields"]["seq"] for e in rounds] == [
        r["seq"] for r in summary["records"]
    ]
    assert len([e for e in events if e["name"] == "engine_decision"]) == (
        summary["decisions_held"]
    )
    # Pre-trace directories contribute no engine lane and never crash.
    assert traceview.engine_trace_snapshot(tmp_path / "absent.json") is None
    # A torn artifact is a load error, not a silent empty lane.
    (tmp_path / "trace.json").write_text("{\"no\": \"records\"}")
    with pytest.raises(traceview.SnapshotLoadError):
        traceview.engine_trace_snapshot(tmp_path / "trace.json")


def test_device_ring_cross_validates_host_recorder_on_differential_scenario(
    tmp_path,
):
    """The acceptance differential: ONE fault schedule through the host
    protocol stack (per-node flight recorders) and through a traced engine
    replay (the ``replay_through_engine`` matched-parameter construction +
    the shared ``inject_engine_event`` mapping). The host cut sequence must
    refine the engine's (the established differential oracle), the ring's
    round-indexed decision sequence must carry exactly the engine's
    decisions, and traceview must render one merged host + ``(chaos)`` +
    ``(engine)`` timeline from the REAL artifact directory."""
    import traceview

    from rapid_tpu.sim import fuzz as simfuzz
    from rapid_tpu.sim.oracles import cuts_refine, inject_engine_event
    from rapid_tpu.types import EdgeStatus

    schedule = simfuzz.scenario_family("crash_during_join", 7)
    result = simfuzz.run_schedule(schedule)
    assert result.final_converged and result.cuts

    vc = VirtualCluster.from_endpoints(
        list(result.endpoints), n_slots=len(result.endpoints),
        n_members=schedule.n0, k=10, h=9, l=4, fd_threshold=1,
        delivery_spread=0, telemetry=True, trace=256,
    )
    expected_members = schedule.n0
    engine_groups = []
    for group in schedule.membership_phases():
        for event in group:
            expected_members += inject_engine_event(vc, event)
        cuts = []
        for _ in range(len(group) + 1):
            was_alive = np.asarray(vc.state.alive)
            _rounds, decided, winner, n_members = vc.run_to_decision(
                max_steps=48
            )
            assert decided, f"engine did not decide for {group}"
            mask = np.asarray(winner)
            cuts.append(frozenset(
                (
                    result.endpoints[s],
                    EdgeStatus.DOWN if was_alive[s] else EdgeStatus.UP,
                )
                for s in np.nonzero(mask)[0].tolist()
            ))
            if n_members == expected_members:
                break
        else:
            raise AssertionError(f"{group} never reached {expected_members}")
        engine_groups.append(cuts)
    assert cuts_refine(result.cuts, engine_groups) is None

    # Same cuts => same round-indexed decision sequence: the ring (sized to
    # hold the whole replay) records one decided round per engine cut, in
    # decode order, each with a registered path code.
    vc.sync()
    ring = vc.trace
    assert ring["rounds_held"] == ring["rounds_recorded"]  # nothing wrapped
    decided_records = [r for r in ring["records"] if r["path"]]
    assert len(decided_records) == sum(len(g) for g in engine_groups)
    assert ring["decisions_held"] == len(decided_records)
    assert all(r["path"] in TRACE_PATH_NAMES for r in decided_records)
    # The host split at most refines engine cuts, never the reverse.
    assert len(result.cuts) >= len(decided_records)

    # The merged timeline from the real artifact dir: host node lanes, the
    # fault-injection lane, AND the device engine lane in one ordering.
    artifacts = tmp_path / "repro"
    simfuzz.write_repro(result, [], artifacts)
    (artifacts / "trace.json").write_text(json.dumps(ring))
    snapshots = traceview.scenario_snapshots(artifacts)
    nodes = {s["node"] for s in snapshots}
    assert traceview.ENGINE_LANE in nodes
    assert traceview.FAULT_LANE in nodes
    assert len(nodes) >= 2 + schedule.n0  # every host node has a lane
    events = traceview.merge_events(snapshots)
    names = {e["name"] for e in events}
    assert "engine_round" in names and "engine_decision" in names
    assert "view_change" in names  # the host recorder's commit events
    engine_decisions = [
        e for e in events if e["name"] == "engine_decision"
    ]
    # The decision events carry the ring's global round ordinal (the
    # recorder's own seq is its per-node event counter, not the round).
    assert [e["fields"]["seq"] for e in engine_decisions] == [
        r["seq"] for r in decided_records
    ]


def test_clustertop_rounds_pane_renders_and_tolerates_torn_snapshots():
    """The ROUNDS pane: one row per decoded ring (cluster label, fleet
    ``node/t<i>`` lanes), dashes for torn records, nothing at all for
    pre-trace snapshots."""
    import clustertop

    vc = _cluster(trace=R, seed=9)
    vc.crash([2, 7])
    vc.run_to_decision(max_steps=32)
    vc.sync()
    snap = vc.telemetry_snapshot()
    snap["node"] = "engine0"
    torn = {"node": "torn", "engine": {
        "trace": {"records": "garbage", "rounds_recorded": None}
    }}
    lines = clustertop.render_rounds_pane([snap, torn, {"node": "old", "engine": {}}])
    assert lines and "ROUNDS" in lines[1]
    body = "\n".join(lines)
    assert "engine0" in body and "torn" in body and "old" not in body
    engine_row = next(l for l in lines if l.startswith("engine0"))
    trace = snap["engine"]["trace"]
    assert str(trace["rounds_recorded"]) in engine_row
    assert TRACE_PATH_NAMES[trace["last_path"]] in engine_row
    torn_row = next(l for l in lines if l.startswith("torn"))
    assert set(torn_row.split()[1:]) == {"-"}
    # No traced snapshot at all: the pane vanishes rather than render empty.
    assert clustertop.render_rounds_pane([{"node": "old", "engine": {}}]) == []
    # The record fields the pane's sparkline walks are the frozen decode
    # vocabulary — a renamed lane breaks here, not silently in a terminal.
    assert all(
        set(TRACE_RECORD_FIELDS) <= set(r) for r in trace["records"]
    )
