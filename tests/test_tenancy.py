"""Tenant-fleet parity: B batched clusters must be bit-identical to B
independent ``VirtualCluster`` runs — the non-negotiable bar (the way
``tests/test_parallel_2d.py`` pinned the 2-D mesh).

The pinned differential grid stacks B=8 tenants compiled from FOUR distinct
sim scenario families (``partition_heal``, ``asymmetric_link``,
``crash_during_join``, ``churn_under_loss``) at two seeds each, with
per-tenant H/L/fd knob mixes, and drives the fleet against per-tenant
singles two ways:

- per STEP (``fleet_step``): the cut sequences, configuration ids, and
  decision rounds must match exactly, tenant by tenant;
- per WAVE (``fleet_wave`` — the lockstep multi-cut loop): every phase
  group's (rounds, cuts, config id, epoch, membership) must match the
  single-cluster ``run_until_membership`` exactly.

Plus the 3-D ``('tenant', 'cohort', 'nodes')`` mesh: rule-table shardings
with the leading tenant axis, mesh-step parity against the single-device
fleet, and the ShardingShapeError/pad_to_multiple discipline for a tenant
count that does not divide the tenant axis.
"""

import functools
import random
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rapid_tpu.models.virtual_cluster import VirtualCluster
from rapid_tpu.protocol.fast_paxos import FastPaxos
from rapid_tpu.types import Endpoint
from rapid_tpu.utils.clock import ManualClock
from rapid_tpu.parallel.mesh import (
    COHORT_AXIS,
    NODE_AXIS,
    TENANT_AXIS,
    ShardingShapeError,
    fleet_state_shardings,
    make_mesh,
    pad_to_multiple,
    shard_fleet_faults,
    shard_fleet_state,
)
from rapid_tpu.sim.oracles import cuts_refine
from rapid_tpu.tenancy import TenantFleet, chaos
from rapid_tpu.tenancy.fleet import knob_shardings

#: The pinned grid: B=8 tenants over four distinct sim families x two seeds,
#: with a per-tenant knob mix (H/L/fd_threshold traced lanes — one compiled
#: fleet program serves every mix).
GRID_SPECS = [
    ("partition_heal", 1), ("partition_heal", 2),
    ("asymmetric_link", 1), ("asymmetric_link", 2),
    ("crash_during_join", 1), ("crash_during_join", 2),
    ("churn_under_loss", 1), ("churn_under_loss", 2),
]
GRID_KNOBS = [
    (9, 4, 1), (8, 3, 1), (7, 2, 1), (9, 4, 1),
    (8, 3, 1), (9, 4, 1), (7, 2, 1), (8, 3, 1),
]


def _drive_single(vc, max_steps):
    """(cuts, config_ids, decision_rounds) of a per-step single-cluster
    drive — the test_parallel_2d labeling ((slot, up/down) cut members)."""
    cuts, ids, rounds = [], [], []
    for i in range(max_steps):
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


def _assert_leaves_equal(got, want, label):
    got_leaves, want_leaves = map(jax.tree_util.tree_leaves, (got, want))
    assert len(got_leaves) == len(want_leaves), label
    for ours, theirs in zip(got_leaves, want_leaves):
        assert ours.dtype == theirs.dtype, label
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs), err_msg=str(label))


def _injected_tenants(telemetry=False):
    """The grid's tenants with EVERY membership phase injected up front
    (maximum overlapped churn; both sides of the parity get the identical
    injections). ``telemetry=True`` carries the device telemetry plane —
    the drive itself must stay bit-identical either way."""
    scenarios = chaos.compile_fleet(
        GRID_SPECS, knobs=GRID_KNOBS, telemetry=telemetry
    )
    for scenario in scenarios:
        for group in scenario.groups:
            chaos._inject_group(scenario.vc, group)
    return scenarios


def test_grid_step_parity_bit_identical():
    singles = _injected_tenants()
    expected = [_drive_single(s.vc, 24) for s in singles]
    assert all(cuts for cuts, _, _ in expected), "grid produced no cuts"

    fleet_side = _injected_tenants()
    fleet = TenantFleet.from_clusters([s.vc for s in fleet_side])
    got_cuts = [[] for _ in fleet_side]
    got_ids = [[] for _ in fleet_side]
    got_rounds = [[] for _ in fleet_side]
    for i in range(24):
        was_alive = np.asarray(fleet.state.alive)
        events = fleet.step()
        decided = np.asarray(events.decided)
        if not decided.any():
            continue
        winners = np.asarray(events.winner_mask)
        ids_now = fleet.config_ids()
        for t in np.nonzero(decided)[0].tolist():
            got_cuts[t].append(frozenset(
                (s, "down" if was_alive[t, s] else "up")
                for s in np.nonzero(winners[t])[0].tolist()
            ))
            got_ids[t].append(ids_now[t])
            got_rounds[t].append(i)

    for t, (cuts, ids, rounds) in enumerate(expected):
        label = fleet_side[t].name
        assert got_rounds[t] == rounds, label
        assert got_ids[t] == ids, label
        assert got_cuts[t] == cuts, label
        # The sim battery's refinement relation as the comparator:
        # bit-identical sequences refine each other in both directions.
        assert cuts_refine(got_cuts[t], [[c] for c in cuts]) is None, label
        assert cuts_refine(cuts, [[c] for c in got_cuts[t]]) is None, label
    # Final states identical tenant by tenant.
    alive = np.asarray(fleet.state.alive)
    for t, scenario in enumerate(singles):
        np.testing.assert_array_equal(
            alive[t], np.asarray(scenario.vc.state.alive)
        )


@pytest.mark.slow
def test_grid_wave_parity_multi_phase():
    """The lockstep fleet wave, phase group by phase group, against the
    nested single-cluster multi-cut loop: (rounds, cuts, config id, epoch,
    membership) per phase and the final alive masks must match exactly —
    and the per-tenant oracle battery is clean on the genuine run.

    Rides the unfiltered check.sh pass (the PR-9 wave-parity precedent):
    tier-1's wall budget keeps the step-parity grid — the acceptance pin —
    and test_tenancy_chaos's genuine fleet run covers the wave path's
    phase-group resolution in-session."""
    fleet_result = chaos.run_fleet(
        chaos.compile_fleet(GRID_SPECS, knobs=GRID_KNOBS)
    )
    assert chaos.check_fleet(fleet_result) == []
    assert fleet_result.total_cuts >= len(GRID_SPECS)  # every tenant cut

    for t, (family, seed) in enumerate(GRID_SPECS):
        scenario = chaos.compile_tenant(family, seed, GRID_KNOBS[t])
        expected = scenario.schedule.n0
        for g, group in enumerate(scenario.groups):
            expected += chaos._inject_group(scenario.vc, group)
            rounds, cuts, resolved, _ = scenario.vc.run_until_membership(
                expected, max_steps=64, max_cuts=8, min_cuts=1,
            )
            record = fleet_result.phases[t][g]
            assert resolved and record.resolved, (scenario.name, g)
            assert record.cuts == cuts, (scenario.name, g)
            assert record.config_id == scenario.vc.config_id, (scenario.name, g)
            assert record.config_epoch == scenario.vc.config_epoch, (
                scenario.name, g,
            )
            assert record.members == scenario.vc.membership_size, (
                scenario.name, g,
            )
        assert fleet_result.final_slots[t] == frozenset(
            np.nonzero(np.asarray(scenario.vc.state.alive))[0].tolist()
        ), scenario.name


# ---------------------------------------------------------------------------
# Knob discipline
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# The gated step (one scalar cond(any(decided)) outside the vmap — the
# program the drivers dispatch) against the lockstep select (vmap of the
# per-cluster step — the mesh program): every leaf equal after every round.
# ---------------------------------------------------------------------------

GATE_TENANTS = 4
#: Which tenants lose a member before round 0, per scenario.
GATE_SCENARIOS = {"none_decides": (), "some_decide": (0, 2), "all_decide": (0, 1, 2, 3)}
GATE_SPELLINGS = {
    "plain": {},
    "telem": {"telemetry": True},
    "trace": {"telemetry": True, "trace": 4},
}


@functools.lru_cache(maxsize=None)
def _lockstep_step(spelling):
    """The lockstep reference for a spelling: ``fleet_step_impl`` itself, or
    the same vmap of the per-cluster step with the observers riding (what
    ``fleet_step_impl`` is to ``engine_step_impl``). One jit a spelling."""
    from rapid_tpu.models import virtual_cluster as vcm
    from rapid_tpu.tenancy import fleet as fleetm

    per_cluster = None if spelling == "plain" else vcm.engine_step_impl

    def step(cfg, state, faults, knobs, *observers):
        if per_cluster is None:
            return fleetm.fleet_step_impl(cfg, state, faults, knobs)
        return jax.vmap(
            lambda st, ft, kn, *obs: per_cluster(
                fleetm._tenant_cfg(cfg, kn), st, *obs, ft
            )
        )(state, faults, knobs, *observers)

    return jax.jit(step, static_argnums=(0,))


@pytest.mark.parametrize("scenario", sorted(GATE_SCENARIOS))
@pytest.mark.parametrize("spelling", sorted(GATE_SPELLINGS))
def test_gated_step_is_bit_identical_to_the_lockstep_step(spelling, scenario):
    victims = GATE_SCENARIOS[scenario]
    fleet = TenantFleet.create(
        GATE_TENANTS, 28, n_slots=32, k=3, cohorts=2,
        knobs=[(3, 1, 2)] * GATE_TENANTS, delivery_spread=1,
        **GATE_SPELLINGS[spelling],
    )
    for t in victims:
        fleet.faults = fleet.faults._replace(
            crashed=fleet.faults.crashed.at[t, 3 + t].set(True)
        )
    reference = _lockstep_step(spelling)
    copy = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)
    ref_state = copy(fleet.state)
    ref_observers = tuple(
        copy(o) for o in (fleet.telem, fleet.trace_ring) if o is not None
    )
    decided_rounds = []
    for _ in range(8):
        events = fleet.step()
        ref_state, *ref_observers, ref_events = reference(
            fleet.cfg, ref_state, fleet.faults, fleet.knobs, *ref_observers
        )
        _assert_leaves_equal(
            (fleet.state, events, fleet.telem, fleet.trace_ring),
            (ref_state, ref_events, *ref_observers), (spelling, scenario),
        )
        decided_rounds.append(np.asarray(events.decided))
    decided_rounds = np.stack(decided_rounds)
    # the scenario is what it says: exactly the victims' tenants decide, and
    # (unless all do at once) some round passes with the gate shut
    assert set(np.nonzero(decided_rounds.any(axis=0))[0].tolist()) == set(victims)
    assert (~decided_rounds.any(axis=1)).any()
    assert int(fleet._gate_rounds[0]) == int(decided_rounds.any(axis=1).sum())


def test_commit_round_counter_rides_the_fetch_boundaries_only():
    """``engine_fleet_commit_rounds``: carried on the device by the streamed
    step (no fetch, no byte moves while waves are submitted) and mirrored at
    the drain; it counts the rounds whose events had any decision."""
    from rapid_tpu.serving.stream import FleetWave, StreamDriver

    fleet = TenantFleet.create(
        4, 28, n_slots=32, k=3, cohorts=2, knobs=[(3, 1, 2)] * 4,
        delivery_spread=1,
    )
    driver = StreamDriver(fleet, rounds_per_wave=8, depth=2)
    seen, stream_step = [], fleet.stream_step

    def recording_step(wave=None):
        seen.append(stream_step(wave=wave))
        return seen[-1]

    fleet.stream_step = recording_step
    d2h = fleet.metrics.counters["engine_d2h_bytes"]
    for crashes in [((0, 3), (2, 5)), ((1, 4),), ((0, 6), (3, 7))]:
        driver.submit(FleetWave(crash=crashes))
        assert fleet.metrics.counters["engine_d2h_bytes"] == d2h
    assert fleet.metrics.counters.get("engine_fleet_commit_rounds", 0) == 0
    driver.drain()
    opened = sum(bool(np.asarray(ev.decided).any()) for ev in seen)
    assert len(seen) == 24 and 3 <= opened < 24
    assert fleet.metrics.counters["engine_fleet_commit_rounds"] == opened
    assert fleet.metrics.counters["engine_d2h_bytes"] > d2h
    tenancy = fleet.telemetry_snapshot()["engine"]["tenancy"]
    assert tenancy["fleet_commit_rounds_total"] == opened


# ---------------------------------------------------------------------------
# The round's own gated arms (``cond_across``): under the meshless programs'
# named batch axis ``invalidation`` and ``classic`` run when SOME tenant needs
# them, for every tenant, each selecting by its own predicate. The two mixed
# cases that select exists for, against separate ``VirtualCluster`` runs (and
# the lockstep select, for the step): every state leaf equal, tenant by tenant.
# ---------------------------------------------------------------------------

ARM_TENANTS = 4
ARM_KW = dict(n_slots=32, k=3, h=3, l=1, cohorts=2, fd_threshold=2, delivery_spread=1)


def _arm_clusters(scenario):
    """Four tenants of the gate tests' geometry. ``invalidation``: tenant 2
    loses a member, the others nothing. ``classic``: tenant 1 (fallback after
    two undecided rounds) has a contested cut, its minority cohort deaf to
    every observer of the second victim; tenant 3 loses one member and
    decides fast; tenants 0 and 2 nothing."""
    clusters = []
    for t in range(ARM_TENANTS):
        low = {"fallback_rounds": 2} if (scenario, t) == ("classic", 1) else {}
        vc = VirtualCluster.create(28, seed=t, **ARM_KW, **low)
        vc.assign_cohorts_roundrobin()
        clusters.append(vc)
    if scenario == "invalidation":
        clusters[2].crash([5])
        return clusters
    contested = clusters[1]
    cohort_of = np.zeros(contested.cfg.n, dtype=np.int32)
    cohort_of[19:] = 1
    contested.assign_cohorts(cohort_of)
    contested.crash([3, 11])
    deaf = np.zeros((contested.cfg.c, contested.cfg.n), dtype=bool)
    deaf[1, np.asarray(contested.state.obs_idx)[:, 11]] = True
    contested.set_rx_block(deaf)
    clusters[3].crash([7])
    return clusters


@pytest.mark.parametrize("verb", ["step", "run_to_decision"])
@pytest.mark.parametrize("scenario", ["invalidation", "classic"])
def test_a_gated_arm_taken_by_one_tenant_leaves_every_tenant_bit_identical(scenario, verb):
    singles = _arm_clusters(scenario)
    fleet = TenantFleet.from_clusters(_arm_clusters(scenario))
    if verb == "run_to_decision":
        rounds, decided, _, members = fleet.run_to_decision(max_steps=12)
        for t, vc in enumerate(singles):
            assert vc.run_to_decision(max_steps=12)[:2] == (rounds[t], decided[t]), t
            assert vc.membership_size == members[t], t
    else:
        reference = _lockstep_step("plain")
        ref_state = jax.tree_util.tree_map(jnp.copy, fleet.state)
        fast = np.zeros((12, ARM_TENANTS), dtype=bool)
        slow = np.zeros((12, ARM_TENANTS), dtype=bool)
        for i in range(12):
            events = fleet.step()
            ref_state, ref_events = reference(fleet.cfg, ref_state, fleet.faults, fleet.knobs)
            _assert_leaves_equal((fleet.state, events), (ref_state, ref_events), i)
            for t, vc in enumerate(singles):
                single = vc.step()
                assert bool(single.decided) == bool(events.decided[t]), (i, t)
            fast[i] = np.asarray(events.fast_decided)
            slow[i] = np.asarray(events.decided) & ~fast[i]
        # the scenario is what it says: who decided, and by which path
        want_fast, want_slow = {"invalidation": ({2}, set()), "classic": ({3}, {1})}[scenario]
        assert set(np.nonzero(fast.any(axis=0))[0].tolist()) == want_fast
        assert set(np.nonzero(slow.any(axis=0))[0].tolist()) == want_slow
    for t, vc in enumerate(singles):
        _assert_leaves_equal(fleet.tenant_state(t), vc.state, (scenario, verb, t))
    # the arm ran, in a round in which the tenants with no fault (not one
    # report bit, so no subject in flux and no proposal) did not need it
    fleet.sync()
    counters = fleet.metrics.counters
    assert counters["engine_fleet_invalidation_rounds"] >= 1
    assert (counters["engine_fleet_classic_rounds"] >= 1) == (scenario == "classic")
    idle = [0, 1, 3] if scenario == "invalidation" else [0, 2]
    assert not np.asarray(fleet.state.report_bits)[idle].any()
    assert not np.asarray(fleet.state.announced)[idle].any()


def test_arm_round_counters_ride_the_fetch_boundaries_only():
    """``engine_fleet_invalidation_rounds`` / ``engine_fleet_classic_rounds``
    on the paper's step (10 crashes in every tenant of 1,000, K,H,L = 10,9,3):
    every tenant decides in the fifth round, some subject is in flux in two
    of the five and no fallback is ever due. Carried on the device by the
    step, mirrored with the commit rounds at the sync and nowhere else; the
    fused decision brings them with the observation it fetches anyway."""
    tenants, members = 3, 1000

    def crashed_fleet():
        fleet = TenantFleet.create(
            tenants, members, n_slots=members, k=10, cohorts=8,
            knobs=[(9, 3, 3)] * tenants, delivery_spread=2,
        )
        victims = np.random.default_rng(5)
        fleet.stream_crash([
            (t, int(slot)) for t in range(tenants)
            for slot in victims.choice(members, 10, replace=False)
        ])
        return fleet

    fleet = crashed_fleet()
    d2h = fleet.metrics.counters["engine_d2h_bytes"]
    decided = [np.asarray(fleet.step().decided) for _ in range(5)]
    assert [d.all() for d in decided] == [False] * 4 + [True]
    assert not any(d.any() for d in decided[:4])
    counters = fleet.metrics.counters
    assert counters["engine_d2h_bytes"] == d2h  # five steps: not one byte fetched
    assert "engine_fleet_invalidation_rounds" not in counters
    fleet.sync()
    assert counters["engine_d2h_bytes"] == d2h + 12  # the one int32[3] fetch
    assert counters["engine_fleet_commit_rounds"] == 1
    assert counters["engine_fleet_invalidation_rounds"] == 2
    assert counters["engine_fleet_classic_rounds"] == 0
    fleet.sync()  # no step since: nothing to fetch
    assert counters["engine_d2h_bytes"] == d2h + 12
    tenancy = fleet.telemetry_snapshot()["engine"]["tenancy"]
    assert tenancy["fleet_invalidation_rounds_total"] == 2
    assert tenancy["fleet_classic_rounds_total"] == 0
    scrape = fleet.prometheus_text()
    assert re.search(r'^rapid_engine_fleet_invalidation_rounds_total\{[^}]*\} 2$', scrape, re.M)
    assert re.search(r'^rapid_engine_fleet_classic_rounds_total\{[^}]*\} 0$', scrape, re.M)

    fused = crashed_fleet()
    d2h = fused.metrics.counters["engine_d2h_bytes"]
    rounds, was_decided, _, _ = fused.run_to_decision(max_steps=16)
    assert rounds.tolist() == [5] * tenants and was_decided.all()
    # the packed observation, two int32 longer; no fetch beside it
    assert fused.metrics.counters["engine_d2h_bytes"] == d2h + 4 * (3 * tenants + 2)
    assert fused.metrics.counters["engine_fleet_invalidation_rounds"] == 2
    assert fused.metrics.counters["engine_fleet_classic_rounds"] == 0


def test_fleet_rejects_mismatched_static_geometry():
    a = VirtualCluster.create(12, n_slots=16, k=4, h=3, l=1, cohorts=2,
                              fd_threshold=1, seed=0)
    b = VirtualCluster.create(12, n_slots=16, k=4, h=3, l=1, cohorts=4,
                              fd_threshold=1, seed=1)
    with pytest.raises(ValueError, match="fleet-static"):
        TenantFleet.from_clusters([a, b])
    # Knob fields may differ freely: same geometry, different H/L/fd.
    c = VirtualCluster.create(12, n_slots=16, k=4, h=2, l=1, cohorts=2,
                              fd_threshold=2, seed=2)
    fleet = TenantFleet.from_clusters([a, c])
    assert fleet.b == 2
    assert fleet.knobs.h.tolist() == [3, 2]
    assert fleet.knobs.fd_threshold.tolist() == [1, 2]


def test_fleet_rejects_invalid_watermarks():
    a = VirtualCluster.create(12, n_slots=16, k=4, h=5, l=1, cohorts=2,
                              fd_threshold=1, seed=0)
    with pytest.raises(ValueError, match="1 <= L <= H <= K"):
        TenantFleet.from_clusters([a])


# ---------------------------------------------------------------------------
# The ('tenant', 'cohort', 'nodes') mesh
# ---------------------------------------------------------------------------

MESH3D_SHAPE = (2, 2, 2)


def make_mesh_3d():
    return make_mesh(jax.devices()[:8], shape=MESH3D_SHAPE)


def _mesh_fleet(b=4, n_members=28, n_slots=32, cohorts=4):
    knobs = [(3, 1, 2), (4, 2, 2), (3, 1, 2), (4, 1, 2)][:b]
    fleet = TenantFleet.create(
        b, n_members, n_slots=n_slots, k=4, cohorts=cohorts, knobs=knobs,
        delivery_spread=1,
    )
    return fleet


def test_fleet_shardings_carry_leading_tenant_axis():
    mesh = make_mesh_3d()
    shardings = fleet_state_shardings(mesh)
    P = jax.sharding.PartitionSpec
    assert shardings.alive.spec == P(TENANT_AXIS, NODE_AXIS)
    assert shardings.report_bits.spec == P(TENANT_AXIS, COHORT_AXIS, NODE_AXIS)
    assert shardings.seen_down.spec == P(TENANT_AXIS, COHORT_AXIS)
    assert shardings.config_epoch.spec == P(TENANT_AXIS)
    assert knob_shardings(mesh).h.spec == P(TENANT_AXIS)
    # Placed leaves genuinely split over all eight devices: a [t, c, n]
    # leaf's per-device shard is 1/8 of global.
    fleet = _mesh_fleet()
    state = shard_fleet_state(fleet.state, mesh)
    for leaf in (state.report_bits, state.released, state.prop_mask):
        shard = leaf.addressable_shards[0].data
        assert shard.nbytes * 8 == leaf.nbytes, leaf.shape
    # [t] per-configuration lanes split over 'tenant' only.
    for leaf in (state.config_epoch, state.n_members):
        shard = leaf.addressable_shards[0].data
        assert shard.nbytes * 2 == leaf.nbytes, leaf.shape


def test_fleet_shard_names_indivisible_tenant_count():
    """Satellite: a tenant count that does not divide the 'tenant' mesh
    axis raises the named error with the pad_to_multiple fix — pad the
    fleet with idle tenants, never an opaque XLA failure."""
    mesh = make_mesh_3d()
    fleet = _mesh_fleet(b=3)
    with pytest.raises(ShardingShapeError) as err:
        shard_fleet_state(fleet.state, mesh)
    msg = str(err.value)
    assert "does not divide" in msg and "pad_to_multiple" in msg
    assert pad_to_multiple(3, MESH3D_SHAPE[0]) == 4
    padded = _mesh_fleet(b=pad_to_multiple(3, MESH3D_SHAPE[0]))
    shard_fleet_state(padded.state, mesh)


@pytest.mark.slow
def test_mesh_fleet_step_parity_against_single_device():
    """The audited fleet3d entrypoints (make_fleet_step/make_fleet_wave on
    the 3-D mesh) produce bit-identical per-tenant results to the
    single-device fleet — which the grid above ties to B independent
    clusters, closing the chain mesh -> fleet -> singles."""
    from rapid_tpu.tenancy.fleet import make_fleet_step, make_fleet_wave

    def crashed_fleet():
        fleet = _mesh_fleet()
        for t in range(fleet.b):
            # Per-tenant fault masks: different victims per tenant.
            crashed = fleet.faults.crashed.at[t, 1 + t].set(True)
            fleet.faults = fleet.faults._replace(crashed=crashed)
        return fleet

    single = crashed_fleet()
    for _ in range(10):
        single.step()
    single_ids = single.config_ids()

    mesh = make_mesh_3d()
    fleet = crashed_fleet()
    step = make_fleet_step(fleet.cfg, mesh)
    state = shard_fleet_state(fleet.state, mesh)
    faults = shard_fleet_faults(fleet.faults, mesh)
    knobs = jax.tree.map(
        lambda x, sh: jax.device_put(x, sh), fleet.knobs, knob_shardings(mesh)
    )
    for _ in range(10):
        state, events = step(state, faults, knobs)
    np.testing.assert_array_equal(
        np.asarray(state.alive), np.asarray(single.state.alive)
    )
    mesh_ids = [
        (int(hi) << 32) | int(lo)
        for hi, lo in zip(np.asarray(state.config_hi), np.asarray(state.config_lo))
    ]
    assert mesh_ids == single_ids

    # And the lockstep wave on the mesh: same multi-tenant resolution in
    # one dispatch.
    single2 = crashed_fleet()
    targets = single2.membership_sizes() - 1
    r1, c1, res1, sizes1 = single2.run_until_membership(
        targets, max_steps=32, max_cuts=4, min_cuts=1
    )
    assert res1.all()
    fleet2 = crashed_fleet()
    wave = make_fleet_wave(fleet2.cfg, mesh, max_cuts=4)
    state2, steps2, cuts2, resolved2, sizes2 = wave(
        shard_fleet_state(fleet2.state, mesh),
        shard_fleet_faults(fleet2.faults, mesh),
        knobs,
        jax.device_put(jnp.asarray(targets),
                       jax.sharding.NamedSharding(
                           mesh, jax.sharding.PartitionSpec(TENANT_AXIS))),
        jnp.int32(32),
        jax.device_put(jnp.ones(fleet2.b, jnp.int32),
                       jax.sharding.NamedSharding(
                           mesh, jax.sharding.PartitionSpec(TENANT_AXIS))),
    )
    assert np.asarray(resolved2).all()
    np.testing.assert_array_equal(np.asarray(steps2), r1)
    np.testing.assert_array_equal(np.asarray(cuts2), c1)
    np.testing.assert_array_equal(np.asarray(sizes2), sizes1)
    np.testing.assert_array_equal(
        np.asarray(state2.alive), np.asarray(single2.state.alive)
    )


# ---------------------------------------------------------------------------
# Decision-path telemetry: the per-tenant fast/classic lane split must speak
# the host protocol's vocabulary (FastPaxos.decided_path: "classic" iff the
# classic fallback's Paxos learner decided) and match B independent clusters
# counter-for-counter on the pinned differential grid.
# ---------------------------------------------------------------------------


def _host_committee_path(n, votes):
    """Drive a fully connected host FastPaxos committee (the test_paxos.py
    DirectNetwork shape: FIFO-pumped direct wiring) over the given per-node
    proposals; if the fast round stalls, one node's fallback fires a classic
    round. Returns the committee's unanimous ``decided_path`` label — the
    vocabulary the engine's decision-path lanes must reproduce."""

    def ep(i):
        return Endpoint("127.0.0.1", 47000 + i)

    instances = {}
    queue, pumping = [], []

    def pump(destination, request):
        queue.append((destination, request))
        if pumping:
            return
        pumping.append(True)
        try:
            while queue:
                dst, req = queue.pop(0)
                targets = (
                    [instances[dst]] if dst is not None
                    else list(instances.values())
                )
                for inst in targets:
                    inst.handle_message(req)
        finally:
            pumping.clear()

    decisions = {}
    clock = ManualClock()
    for i in range(n):
        addr = ep(i)
        instances[addr] = FastPaxos(
            my_addr=addr, configuration_id=1, membership_size=n,
            broadcast_fn=lambda req: pump(None, req),
            send_fn=pump,
            on_decide=lambda hosts, a=addr: decisions.setdefault(
                a, tuple(hosts)
            ),
            clock=clock, rng=random.Random(i),
        )
    for i, proposal in enumerate(votes):
        instances[ep(i)].propose(proposal, recovery_delay_ms=1e9)
    if not decisions:
        instances[ep(0)].start_classic_paxos_round()
    assert len(decisions) == n and len(set(decisions.values())) == 1
    paths = {inst.decided_path for inst in instances.values()}
    assert len(paths) == 1
    return paths.pop()


def test_decision_path_lanes_speak_the_host_fast_paxos_vocabulary():
    """Matched host/engine contention shapes land on the same path label.

    Host side: a unanimous committee decides with ``decided_path == "fast"``;
    a split committee (no fast quorum) decides through the fallback with
    ``decided_path == "classic"`` (fast_paxos.py: "classic" iff the inner
    Paxos decided). Engine side: the same two contention shapes must place
    their decision in the matching telemetry lane — the round body's
    ``fb_decided`` is gated on ``~fast_decided`` (fallback_due), so the lanes
    are mutually exclusive exactly like the host label."""

    def ep(i):
        return Endpoint("127.0.0.1", 47000 + i)

    # Host labels for the two shapes.
    unanimous = [(ep(9999),)] * 10
    assert _host_committee_path(10, unanimous) == "fast"
    split = [(ep(9999),)] * 7 + [(ep(8888),)] * 3  # quorum(10)=8: stalls
    assert _host_committee_path(10, split) == "classic"

    # Engine, unanimous shape: one crash every cohort agrees on.
    vc = VirtualCluster.create(16, fd_threshold=2, seed=3, telemetry=True)
    vc.crash([5])
    rounds, events = vc.run_until_converged(max_steps=32)
    assert events is not None and bool(events.fast_decided)
    vc.sync()
    activity = vc.activity
    assert activity["decisions_fast"] == 1
    assert activity["decisions_classic"] == 0
    assert activity["fast_path_share"] == 1.0

    # Engine, split shape (the test_engine.py contested-round scenario with
    # the telemetry plane on): cohort 1 never hears the second victim's
    # observers, so its subset proposal denies the fast round its quorum and
    # the classic fallback decides the plurality cut.
    n = 120
    vc = VirtualCluster.create(n, fd_threshold=2, seed=11, telemetry=True)
    cohort_of = np.zeros(n, dtype=np.int32)
    cohort_of[80:] = 1
    vc.assign_cohorts(cohort_of)
    v1, v2 = 10, 60
    vc.crash([v1, v2])
    rx = np.zeros((vc.cfg.c, vc.cfg.n), dtype=bool)
    rx[1, np.asarray(vc.state.obs_idx)[:, v2]] = True
    vc.set_rx_block(rx)
    rounds, events = vc.run_until_converged(max_steps=64)
    assert events is not None and not bool(events.fast_decided)
    vc.sync()
    activity = vc.activity
    assert activity["decisions_classic"] == 1
    assert activity["decisions_fast"] == 0
    assert activity["fast_path_share"] == 0.0
    # Every announced-but-undecided round before the fallback landed is a
    # conflict round; the fallback timer alone guarantees several.
    assert activity["conflict_rounds"] >= vc.cfg.fallback_rounds


def test_grid_decision_path_split_fleet_matches_singles():
    """Per-tenant fast/classic counters on the differential grid: the fleet's
    ``tenant_activity`` must match (a) the host-vocabulary labels recorded
    from each single's per-decision ``events.fast_decided`` and (b) the
    single's own fetched lanes, digest field by digest field."""
    singles = _injected_tenants(telemetry=True)
    expected = []
    for scenario in singles:
        fast = classic = 0
        for _ in range(24):
            events = scenario.vc.step()
            if bool(events.decided):
                # The host label ("classic" iff the classic fallback
                # decided); the engine's paths are mutually exclusive.
                if bool(events.fast_decided):
                    fast += 1
                else:
                    classic += 1
        scenario.vc.sync()
        activity = scenario.vc.activity
        assert activity["decisions_fast"] == fast, scenario.name
        assert activity["decisions_classic"] == classic, scenario.name
        expected.append((fast, classic, activity))
    assert sum(f + c for f, c, _ in expected), "grid produced no decisions"

    fleet_side = _injected_tenants(telemetry=True)
    fleet = TenantFleet.from_clusters([s.vc for s in fleet_side])
    for _ in range(24):
        fleet.step()
    fleet.sync()
    tenant_activity = fleet.tenant_activity
    digest_fields = tuple(expected[0][2])
    for t, (fast, classic, single_activity) in enumerate(expected):
        label = fleet_side[t].name
        got = tenant_activity[t]
        assert got["decisions_fast"] == fast, label
        assert got["decisions_classic"] == classic, label
        for field in digest_fields:
            assert got[field] == single_activity[field], (label, field)
    # The pooled aggregate recomputes the share over the summed split.
    pooled = fleet.activity
    total_fast = sum(f for f, _, _ in expected)
    total = sum(f + c for f, c, _ in expected)
    assert pooled["decisions_fast"] == total_fast
    assert pooled["fast_path_share"] == pytest.approx(total_fast / total)
