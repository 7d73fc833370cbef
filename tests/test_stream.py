"""Streaming serving pipeline (rapid_tpu/serving): the streamed path must be
BIT-IDENTICAL to the batch path — the non-negotiable bar, the way
tests/test_tenancy.py pinned the fleet and tests/test_parallel_2d.py pinned
the 2-D mesh.

The pinned differential drives the SAME seeded Poisson churn schedule two
ways — wave by wave through ``StreamDriver`` (enqueue-only dispatches,
double-buffered deltas, sync only at fetch boundaries) and through the
pre-built batch seams (``crash``/``inject_join_wave`` + ``step``) — and
requires identical cuts, configuration ids, and final state pytrees, for
both the single-cluster and fleet paths. Only the synchronization structure
differs between the two drives; the compiled programs, inputs, and program
order are the same, so any divergence is a pipeline bug.

Budget (the PR-10 convention): the small-grid cluster+fleet differential is
the compile-bearing tier-1 representative; the larger grids (more waves,
more seeds, join-heavy schedules, wider fleets) ride the unfiltered
check.sh pass behind ``slow``.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import membership_model
from benchmarks.generators import churn
from rapid_tpu.models.virtual_cluster import VirtualCluster
from rapid_tpu.serving import (
    STREAMABLE_KINDS,
    FleetPoissonChurn,
    FleetWave,
    PoissonChurn,
    StreamDriver,
    StreamWave,
    waves_from_schedule,
)
from rapid_tpu.sim.faults import FaultEvent
from rapid_tpu.tenancy import TenantFleet


def _cluster(n=24, n_slots=40, seed=0):
    vc = VirtualCluster.create(
        n, n_slots=n_slots, k=3, h=3, l=1, cohorts=2, fd_threshold=2,
        seed=seed,
    )
    vc.assign_cohorts_roundrobin()
    return vc


def _fleet(b=3, n=16, seed0=10):
    clusters = []
    for i in range(b):
        vc = VirtualCluster.create(
            n, k=3, h=3, l=1, cohorts=2, fd_threshold=2, seed=seed0 + i
        )
        vc.assign_cohorts_roundrobin()
        clusters.append(vc)
    return TenantFleet.from_clusters(clusters)


def _trees_equal(a, b) -> bool:
    return bool(jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b
    )))


def _batch_drive_cluster(vc, waves, rounds_per_wave):
    """The batch spelling of a stream schedule: pre-built per-wave deltas
    through the ordinary injection seams, per-round ``step`` dispatches,
    cut labels observed per round (the test_tenancy labeling)."""
    cuts, ids = [], []
    for wave in waves:
        if wave.crash:
            vc.crash(list(wave.crash))
        if wave.join:
            vc.inject_join_wave(list(wave.join))
        for _ in range(rounds_per_wave):
            was_alive = np.asarray(vc.state.alive)
            events = vc.step()
            if bool(events.decided):
                mask = np.asarray(events.winner_mask)
                cuts.append(frozenset(
                    (s, "down" if was_alive[s] else "up")
                    for s in np.nonzero(mask)[0].tolist()
                ))
                ids.append(vc.config_id)
    return cuts, ids


def _stream_seam_drive_cluster(vc, waves, rounds_per_wave):
    """The same schedule through the STREAM seams (fetch-free
    ``stream_step``, admissibility check skipped by the generator
    contract), retaining every round's device-resident events and fetching
    them only AFTER the drive — the pipeline discipline a caller that
    wants per-cut observability without per-round syncs would use."""
    retained = []
    for wave in waves:
        if wave.crash:
            vc.crash(list(wave.crash))
        if wave.join:
            vc.inject_join_wave(list(wave.join), check_admissible=False)
        for _ in range(rounds_per_wave):
            # Retain a device-side COPY: engine_step donates the state
            # pytree, so the live buffer would be deleted by the next
            # round. The copy is an enqueued dispatch — still no fetch.
            alive_before = jnp.copy(vc.state.alive)
            retained.append((alive_before, vc.stream_step()))
    cuts, ids = [], []
    epoch_seen = 0
    for alive_before, events in retained:
        if not bool(events.decided):  # post-drive fetch: the drive is done
            continue
        was_alive = np.asarray(alive_before)
        mask = np.asarray(events.winner_mask)
        cuts.append(frozenset(
            (s, "down" if was_alive[s] else "up")
            for s in np.nonzero(mask)[0].tolist()
        ))
        epoch_seen += 1
    return cuts, epoch_seen


def test_streamed_cluster_is_bit_identical_to_batch():
    """The tier-1 representative (grid variants ride ``slow``): one seeded
    Poisson schedule, three drives — StreamDriver, the stream seams with
    retained events, and the batch path — identical cuts, config ids, and
    final state+faults pytrees."""
    waves = PoissonChurn(24, 40, rate=1.0, seed=7).waves(6)

    streamed = _cluster()
    driver = StreamDriver(streamed, rounds_per_wave=4, depth=2)
    for wave in waves:
        driver.submit(wave)
    result = driver.drain()

    batch = _cluster()
    batch_cuts, batch_ids = _batch_drive_cluster(batch, waves, 4)
    assert batch_cuts, "schedule produced no cuts — the differential is vacuous"

    seams = _cluster()
    seam_cuts, seam_epochs = _stream_seam_drive_cluster(seams, waves, 4)

    # Final state AND faults pytrees: every leaf bit-identical.
    assert _trees_equal(streamed.state, batch.state)
    assert _trees_equal(streamed.faults, batch.faults)
    assert _trees_equal(seams.state, batch.state)
    # Config chain: the id is a hash chain over the whole cut history, so
    # equality here pins the entire view-change sequence.
    assert streamed.config_id == batch.config_id == seams.config_id
    assert streamed.config_epoch == batch.config_epoch
    # The cut sequences observed per round agree exactly.
    assert seam_cuts == batch_cuts
    assert seam_epochs == len(batch_cuts)
    # And the drained stream report agrees with the batch-side count.
    assert result.cuts == len(batch_cuts)
    assert result.waves == 6 and result.rounds == 24


def test_streamed_fleet_is_bit_identical_to_batch():
    """The fleet-path tier-1 representative: per-tenant Poisson crash
    streams through StreamDriver vs the batch fleet seams — identical
    per-tenant config ids, epochs, and final stacked pytrees."""
    waves = FleetPoissonChurn(3, 16, rate=0.7, seed=3).waves(5)

    streamed = _fleet()
    driver = StreamDriver(streamed, rounds_per_wave=3, depth=2)
    for wave in waves:
        driver.submit(wave)
    result = driver.drain()

    batch = _fleet()
    for wave in waves:
        if wave.crash:
            batch.stream_crash(wave.crash)
        for _ in range(3):
            batch.step()

    assert _trees_equal(streamed.state, batch.state)
    assert _trees_equal(streamed.faults, batch.faults)
    assert streamed.config_ids() == batch.config_ids()
    np.testing.assert_array_equal(
        streamed.config_epochs(), batch.config_epochs()
    )
    assert result.cuts == int(batch.config_epochs().sum())
    assert result.waves == 5


# ---------------------------------------------------------------------------
# The cluster trickle's twin: the cell's own arrivals at cohort counts that
# leave spare bits in the last cohort word (8) and that fill their words
# (32, 64), streamed through the carried step
# ---------------------------------------------------------------------------

#: ``cluster-100k.trickle`` at a few hundred members: the configuration's
#: {K, H, L}, detector, spread and coordinators, ``trickle.json``'s waves
#: (Poisson 8 events a wave, a wave all joins or all crashes, 8 rounds a
#: wave, two waves in flight), spare slots for the joins.
TRICKLE = dict(members=300, slots=380, rounds_per_wave=8, depth=2, waves=16)


def _trickle_cluster(cohorts):
    vc = VirtualCluster.create(
        TRICKLE["members"], n_slots=TRICKLE["slots"], k=10, h=9, l=4,
        cohorts=cohorts, fd_threshold=3, seed=46, delivery_spread=2,
        concurrent_coordinators=2,
    )
    vc.assign_cohorts_roundrobin()
    vc.stagger_fd_counts(np.random.default_rng(46), 3)
    return vc


def _trickle_waves():
    """One cycle of the cell's arrivals as ``(crash, join)`` pair arrays."""
    source = churn.PoissonChurn(
        TRICKLE["members"], TRICKLE["slots"], 8.0, 0.5, TRICKLE["waves"],
        20180711, np.random.SeedSequence(46),
    )
    return list(source.cycle())


def _view(vc) -> dict:
    state = vc.state
    return {
        "alive": np.asarray(state.alive)[None],
        "epoch": np.asarray(state.config_epoch)[None],
        "config_hi": np.asarray(state.config_hi)[None],
        "config_lo": np.asarray(state.config_lo)[None],
    }


def _model_checks(model, before, view, cuts_counted) -> dict:
    """The stream generator's seven numbers (``benchmarks/generators/
    stream.py``), every one a count of violations."""
    numbers = model.compare_view(view["alive"])
    numbers.update(model.compare_epochs(before, view))
    numbers["unresolved"] = int((view["alive"].sum(axis=1) != model.sizes()).sum())
    numbers["cut_sizes_unaccounted"] = abs(
        int((view["epoch"] - before["epoch"]).sum()) - cuts_counted)
    assert set(numbers) == set(membership_model.LIMITS)
    return numbers


@pytest.mark.parametrize("cohorts", [8, 32, 64])
def test_streamed_trickle_twin_holds_the_model_after_every_wave(cohorts):
    """Joins and crashes in ONE stream through the carried step and the
    build program, at cohort counts with and without a spare bit in their
    last word: the pipelined stream, a stream drained and checked after
    every wave, and the batch seam agree bit for bit (state, faults, cuts a
    wave, configuration ids), and the membership model's seven checks read
    0 after every wave."""
    waves = _trickle_waves()
    assert any(len(join) for _, join in waves) and any(len(crash) for crash, _ in waves)

    def as_wave(crash, join):
        return StreamWave(crash=tuple(crash[:, 1].tolist()), join=tuple(join[:, 1].tolist()))

    pipelined = _trickle_cluster(cohorts)
    driver = StreamDriver(pipelined, TRICKLE["rounds_per_wave"], TRICKLE["depth"])
    for crash, join in waves:
        driver.submit(as_wave(crash, join))
    result = driver.drain()

    checked = _trickle_cluster(cohorts)
    before = _view(checked)
    model = membership_model.MembershipModel(before["alive"])
    driver = StreamDriver(checked, TRICKLE["rounds_per_wave"], TRICKLE["depth"])
    stream_cuts, stream_ids = [], []
    for w, (crash, join) in enumerate(waves):
        model.apply(crash, join)
        driver.submit(as_wave(crash, join))
        stream_cuts.append(driver.drain().cuts)
        stream_ids.append(checked.config_id)
        numbers = _model_checks(model, before, _view(checked), stream_cuts[-1])
        assert membership_model.failures(numbers) == 0, (w, numbers)

    batch = _trickle_cluster(cohorts)
    batch_cuts, batch_ids, total = [], [], 0
    for crash, join in waves:
        cuts, _ = _batch_drive_cluster(batch, [as_wave(crash, join)], TRICKLE["rounds_per_wave"])
        total += len(cuts)
        batch_cuts.append(total)
        batch_ids.append(batch.config_id)

    assert batch_cuts[-1] >= len(waves) - 2, "the trickle cut nearly nothing: vacuous"
    assert stream_cuts == batch_cuts and stream_ids == batch_ids
    assert result.cuts == batch_cuts[-1]
    for streamed in (pipelined, checked):
        assert _trees_equal(streamed.state, batch.state)
        assert _trees_equal(streamed.faults, batch.faults)
        assert streamed.config_id == batch.config_id
    # both forms of the carried masks ran: the build program after every
    # injection, the step's own rebuild (reused by the next round) after a cut
    counters = pipelined.metrics.counters
    assert counters["engine_edge_mask_builds"] >= len(waves) - 2
    assert counters["engine_edge_mask_reuses"] > counters["engine_edge_mask_builds"]
    jax.clear_caches()  # tier-1 runs near the process's limit of memory maps


@pytest.mark.slow
def test_streamed_cluster_grid_bit_identical():
    """The larger differential grid: seeds x rates x pipeline depths,
    join-heavy and crash-heavy mixes. Rides the unfiltered check.sh pass;
    tier-1's wall budget keeps the single-point cluster differential
    (test_streamed_cluster_is_bit_identical_to_batch) as the acceptance
    pin."""
    for seed, rate, depth, join_fraction in [
        (1, 0.5, 1, 0.8), (2, 2.0, 3, 0.5), (3, 1.5, 2, 0.1),
    ]:
        waves = PoissonChurn(
            24, 40, rate=rate, seed=seed, join_fraction=join_fraction
        ).waves(8)
        streamed = _cluster()
        driver = StreamDriver(streamed, rounds_per_wave=4, depth=depth)
        for wave in waves:
            driver.submit(wave)
        driver.drain()
        batch = _cluster()
        _batch_drive_cluster(batch, waves, 4)
        label = f"seed={seed} rate={rate} depth={depth}"
        assert _trees_equal(streamed.state, batch.state), label
        assert streamed.config_id == batch.config_id, label


@pytest.mark.slow
def test_streamed_fleet_grid_bit_identical():
    """Wider fleet differential (more tenants, more waves, deeper
    pipeline). Rides the unfiltered check.sh pass; tier-1 keeps
    test_streamed_fleet_is_bit_identical_to_batch as the acceptance pin."""
    for seed, rate, depth in [(11, 0.3, 1), (12, 1.0, 4)]:
        waves = FleetPoissonChurn(3, 16, rate=rate, seed=seed).waves(10)
        streamed = _fleet()
        driver = StreamDriver(streamed, rounds_per_wave=3, depth=depth)
        for wave in waves:
            driver.submit(wave)
        driver.drain()
        batch = _fleet()
        for wave in waves:
            if wave.crash:
                batch.stream_crash(wave.crash)
            for _ in range(3):
                batch.step()
        label = f"seed={seed} rate={rate} depth={depth}"
        assert _trees_equal(streamed.state, batch.state), label
        assert streamed.config_ids() == batch.config_ids(), label


# ---------------------------------------------------------------------------
# The churn generators: pure functions of their seed
# ---------------------------------------------------------------------------


def test_poisson_churn_is_deterministic_per_seed():
    a = PoissonChurn(24, 40, rate=1.5, seed=42).waves(20)
    b = PoissonChurn(24, 40, rate=1.5, seed=42).waves(20)
    assert a == b
    c = PoissonChurn(24, 40, rate=1.5, seed=43).waves(20)
    assert a != c  # a different seed is a different schedule


def test_poisson_churn_respects_slot_lifecycle():
    # Fresh slots are never reused (the engine's UUID discipline — what
    # lets the stream skip the admissibility fetch) and crash victims are
    # only ever original members still standing.
    churn = PoissonChurn(24, 40, rate=3.0, seed=9)
    joined, crashed = set(), set()
    for wave in churn.waves(40):
        for slot in wave.join:
            assert slot not in joined and 24 <= slot < 40
            joined.add(slot)
        for slot in wave.crash:
            assert slot not in crashed and 0 <= slot < 24
            crashed.add(slot)


def test_fleet_poisson_churn_deterministic_and_in_range():
    a = FleetPoissonChurn(4, 16, rate=0.8, seed=5).waves(12)
    b = FleetPoissonChurn(4, 16, rate=0.8, seed=5).waves(12)
    assert a == b
    seen = set()
    for wave in a:
        for tenant, slot in wave.crash:
            assert 0 <= tenant < 4 and 0 <= slot < 16
            assert (tenant, slot) not in seen  # no double-crash per tenant
            seen.add((tenant, slot))


def test_generator_validation():
    with pytest.raises(ValueError):
        PoissonChurn(24, 40, rate=0.0)
    with pytest.raises(ValueError):
        PoissonChurn(24, 40, rate=1.0, join_fraction=1.5)
    with pytest.raises(ValueError):
        PoissonChurn(41, 40, rate=1.0)
    with pytest.raises(ValueError):
        FleetPoissonChurn(0, 16, rate=1.0)


def test_waves_from_schedule_speaks_the_sim_fault_vocabulary():
    events = [
        FaultEvent(kind="crash", slots=(1, 2)),
        FaultEvent(kind="join", slots=(24,)),
    ]
    waves = waves_from_schedule(events)
    assert waves == [StreamWave(crash=(1, 2)), StreamWave(join=(24,))]
    # Round trip: StreamWave.fault_events is the exact inverse.
    assert [e for w in waves for e in w.fault_events()] == events
    # settle=False events OVERLAP with their successor — they fold into
    # ONE wave (the whole delta applies before any engine round), never
    # serialize into convergence-separated waves the schedule forbade.
    overlapped = [
        FaultEvent(kind="crash", slots=(3,), settle=False),
        FaultEvent(kind="join", slots=(25,)),
        FaultEvent(kind="crash", slots=(4,)),
    ]
    merged = waves_from_schedule(overlapped)
    assert merged == [
        StreamWave(crash=(3,), join=(25,)),
        StreamWave(crash=(4,)),
    ]
    # ...and the round trip re-emits the overlap, not a settled rewrite.
    assert [e for w in merged for e in w.fault_events()] == overlapped
    # A trailing settle=False event still closes the final wave (it needs
    # its engine rounds even with nothing left to overlap with).
    assert waves_from_schedule(
        [FaultEvent(kind="crash", slots=(5,), settle=False)]
    ) == [StreamWave(crash=(5,))]
    # Everything the stream cannot represent is rejected loudly, never
    # silently dropped — a stream missing a partition event or a dwell is
    # a DIFFERENT scenario.
    with pytest.raises(ValueError, match="not streamable"):
        waves_from_schedule(
            [FaultEvent(kind="loss", slots=(), args={"permille": 50})]
        )
    with pytest.raises(ValueError, match="dwell_ms"):
        waves_from_schedule(
            [FaultEvent(kind="crash", slots=(1,), dwell_ms=250.0)]
        )
    assert STREAMABLE_KINDS == {"crash", "join"}


# ---------------------------------------------------------------------------
# Pipeline mechanics
# ---------------------------------------------------------------------------


def test_stream_driver_backpressure_bounds_waves_in_flight():
    vc = _cluster()
    driver = StreamDriver(vc, rounds_per_wave=2, depth=2)
    for wave in PoissonChurn(24, 40, rate=0.5, seed=1).waves(7):
        driver.submit(wave)
        assert len(driver._pending) <= 2  # the depth bound IS the backpressure
    result = driver.drain()
    assert driver.waves_completed == driver.waves_submitted == 7
    assert len(driver._pending) == 0
    assert result.overlap_efficiency is None or 0.0 <= result.overlap_efficiency <= 1.0


def test_stream_driver_rejects_mismatched_wave_types():
    vc = _cluster()
    cluster_driver = StreamDriver(vc)
    with pytest.raises(TypeError, match="FleetWave"):
        cluster_driver.submit(FleetWave(crash=((0, 1),)))
    fleet_driver = StreamDriver(_fleet())
    with pytest.raises(TypeError, match="StreamWave"):
        fleet_driver.submit(StreamWave(crash=(1,)))
    with pytest.raises(ValueError):
        StreamDriver(vc, rounds_per_wave=0)
    with pytest.raises(ValueError):
        StreamDriver(vc, depth=0)


def test_stream_metrics_and_snapshot_surface():
    vc = _cluster()
    driver = StreamDriver(vc, rounds_per_wave=2, depth=2)
    pre = driver.snapshot()
    # Pre-traffic snapshot: stable key set, None rates (exposition renders
    # NaN so the series set never changes shape).
    assert pre["waves_submitted"] == 0 and pre["view_changes_per_sec"] is None
    for wave in PoissonChurn(24, 40, rate=1.0, seed=2).waves(4):
        driver.submit(wave)
    result = driver.drain()
    snap = driver.snapshot()
    assert snap["waves_submitted"] == snap["waves_completed"] == 4
    assert snap["waves_in_flight"] == 0
    assert snap["view_changes_per_sec"] is not None
    assert vc.metrics.counters["engine_stream_waves"] == 4
    assert vc.metrics.counters["engine_stream_cuts"] == result.cuts
    # The alert->commit latencies land in the shared bounded instrument.
    assert vc.metrics.timings["engine_stream_alert_to_commit"].count == 4
    # The pipeline's dispatch accounting: enqueues under stream_enqueue,
    # sync boundaries under stream_fetch — nothing else.
    family = vc.metrics.phase_timings["engine_dispatch"]
    assert family["stream_enqueue"].count == 8  # 4 waves x 2 rounds
    assert family["stream_fetch"].count >= 1  # the drain boundary
    # The whole snapshot is scrape-ready (clustertop / --metrics-dump).
    json.dumps(vc.telemetry_snapshot())


def test_stream_join_wave_skips_admissibility_fetch():
    # The generator owns the slot bookkeeping, so the streamed join must
    # not pay the [j]-bool device->host fetch (it would stall every
    # enqueued wave behind it); the batch spelling keeps the check.
    vc = _cluster()
    d2h0 = vc.metrics.counters["engine_d2h_bytes"]
    vc.inject_join_wave([30, 31], check_admissible=False)
    assert vc.metrics.counters["engine_d2h_bytes"] == d2h0
    vc2 = _cluster()
    d2h0 = vc2.metrics.counters["engine_d2h_bytes"]
    vc2.inject_join_wave([30, 31])
    assert vc2.metrics.counters["engine_d2h_bytes"] == d2h0 + 2
    with pytest.raises(ValueError, match="not admissible"):
        vc2.inject_join_wave([30])  # already pending: the check still bites


def test_a_small_wave_of_an_unseen_size_compiles_nothing():
    """The stream's waves are a handful of events and every count occurs
    sooner or later (a cell whose spare slots run out turns its join waves
    into crash waves of the joins' sizes): up to ``SMALL_WAVE_SLOTS`` events
    go up at ONE length, so a size this process never saw, crashes, joins or
    both in one wave, dispatches the programs the first waves compiled."""
    from rapid_tpu.models.virtual_cluster import SMALL_WAVE_SLOTS
    from rapid_tpu.utils import engine_telemetry

    vc = _cluster()
    driver = StreamDriver(vc, rounds_per_wave=4, depth=2)
    driver.submit(StreamWave(crash=(1, 2, 3)))
    driver.submit(StreamWave(join=(30, 31)))
    first = driver.drain()
    assert first.cuts >= 1
    seen = engine_telemetry.compile_snapshot()["compiles"]
    driver.submit(StreamWave(crash=(4,), join=(32, 33, 34)))
    driver.submit(StreamWave(crash=(5, 6, 7, 8, 9)))
    driver.submit(StreamWave(join=tuple(range(35, 40))))
    assert driver.drain().cuts > first.cuts
    assert engine_telemetry.compile_snapshot()["compiles"] == seen
    alive = np.asarray(vc.state.alive)
    assert not alive[[1, 2, 3, 4, 5, 6, 7, 8, 9]].any() and alive[30:40].all()
    # a batch seam's wave goes up as it is
    h2d = vc.metrics.counters["engine_h2d_bytes"]
    vc.crash(list(range(10, 10 + SMALL_WAVE_SLOTS)))
    assert vc.metrics.counters["engine_h2d_bytes"] == h2d + 4 * SMALL_WAVE_SLOTS
    vc.crash([0])
    assert vc.metrics.counters["engine_h2d_bytes"] == h2d + 8 * SMALL_WAVE_SLOTS


def test_stream_driver_enforces_admissibility_host_side():
    # The driver mirrors the slot lifecycle on host (ONE pre-stream fetch,
    # pure bookkeeping per wave): a schedule-derived join of a reused slot
    # raises the SAME error the batch path fetches [j] bools to produce —
    # for every wave source, not just PoissonChurn's fresh-slot contract.
    vc = _cluster()
    driver = StreamDriver(vc, rounds_per_wave=1, depth=2)
    with pytest.raises(ValueError, match="not admissible"):
        driver.submit(StreamWave(join=(3,)))  # already a member
    driver.submit(StreamWave(crash=(5,), join=(30,)))
    with pytest.raises(ValueError, match="not admissible"):
        driver.submit(StreamWave(join=(30,)))  # pending from the last wave
    with pytest.raises(ValueError, match="not admissible"):
        driver.submit(StreamWave(join=(5,)))  # crashed slots never rejoin
    driver.drain()


def test_empty_wave_has_no_schedule_spelling():
    # Poisson pacing waves (k=0 draws) cannot serialize: the schedule
    # grammar forbids membership events without slots, and dropping the
    # wave would replay fewer engine rounds than the stream ran. Loud,
    # never silent (the waves_from_schedule discipline, in reverse).
    with pytest.raises(ValueError, match="empty wave"):
        StreamWave().fault_events()


def test_drain_rate_math_is_well_defined_for_degenerate_streams():
    """ISSUE 15 satellite: zero-wave and zero-elapsed drains report rate
    0.0 and a well-defined StreamResult — no div-by-~0 inf/NaN can leak
    into bench JSON, and 0.0 (drained, nothing to rate) stays distinct
    from the pre-drain snapshot's None (not yet drained)."""
    import math

    # Zero waves: nothing ever submitted, wall is exactly 0.
    vc = _cluster()
    result = StreamDriver(vc, rounds_per_wave=2, depth=2).drain()
    assert result.waves == 0 and result.rounds == 0 and result.cuts == 0
    assert result.wall_ms == 0.0
    assert result.view_changes_per_sec == 0.0
    assert result.p99_alert_to_commit_ms is None
    assert result.overlap_efficiency is None  # unmeasurable, not fake
    for value in result:
        assert not (isinstance(value, float) and (
            math.isnan(value) or math.isinf(value)
        ))
    json.dumps(vc.telemetry_snapshot())

    # Zero elapsed: a frozen injected clock makes wall_ms exactly 0 even
    # WITH traffic — the rate must still be 0.0, never cuts/0 = inf.
    vc2 = _cluster()
    frozen = StreamDriver(vc2, rounds_per_wave=2, depth=2, clock=lambda: 5.0)
    for wave in PoissonChurn(24, 40, rate=1.0, seed=2).waves(3):
        frozen.submit(wave)
    result2 = frozen.drain()
    assert result2.wall_ms == 0.0
    assert result2.view_changes_per_sec == 0.0
    assert result2.overlap_efficiency is None
    for value in result2:
        assert not (isinstance(value, float) and (
            math.isnan(value) or math.isinf(value)
        ))


def test_fleet_stream_crash_bounds_checked():
    fleet = _fleet()
    with pytest.raises(IndexError):
        fleet.stream_crash([(3, 0)])  # tenant out of range
    with pytest.raises(IndexError):
        fleet.stream_crash([(0, 16)])  # slot out of range
