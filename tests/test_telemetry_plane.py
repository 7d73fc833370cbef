"""Device telemetry plane: telemetry=1 must be PURE observation.

The non-negotiable bar (the ISSUE's hard acceptance line): a telemetry=1
engine produces bit-identical results — full state/fault pytrees, cut
sequences, configuration-id chains, decision rounds — to the telemetry=0
engine on every driver spelling (per-step, fused convergence, multi-cut
wave, fleet lockstep, streaming pipeline). The lanes themselves must be
path-independent: the fused ``run_to_decision`` while-loop (lanes riding) and a
per-step drive accumulate the same counters, and a fleet tenant's lanes
match a per-cluster drive exactly (the wave's coast-gating pin promised in
``fleet_wave_impl``'s docstring).

Budget (the PR-10 convention): the small-grid cluster+fleet+stream
differentials are the compile-bearing tier-1 representatives; the larger
geometry grid rides the unfiltered check.sh pass behind ``slow``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rapid_tpu.models.virtual_cluster import VirtualCluster
from rapid_tpu.serving import PoissonChurn, StreamDriver
from rapid_tpu.tenancy import TenantFleet
from rapid_tpu.utils.engine_telemetry import TELEMETRY_DIGEST_FIELDS


def _cluster(telemetry, n=24, n_slots=40, seed=0, **kw):
    vc = VirtualCluster.create(
        n, n_slots=n_slots, k=3, h=3, l=1, cohorts=2, fd_threshold=2,
        seed=seed, telemetry=telemetry, **kw,
    )
    vc.assign_cohorts_roundrobin()
    return vc


def _trees_equal(a, b) -> bool:
    return bool(jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b
    )))


def _lanes_host(telem):
    return jax.tree_util.tree_map(lambda x: np.asarray(x), telem)


def _churn_drive(vc, steps=10):
    """Crash + join churn through the per-step seam; the test_tenancy cut
    labeling, so both sides of every differential observe identically."""
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


def test_step_drive_bit_identical_telemetry_on_off():
    """The tier-1 representative: one crash+join churn drive, telemetry on
    vs off — identical cuts, config-id chains, decision rounds, and final
    state AND fault pytrees, leaf for leaf."""
    off = _cluster(telemetry=False)
    on = _cluster(telemetry=True)
    expected = _churn_drive(off)
    got = _churn_drive(on)
    assert expected[0], "drive produced no cuts — the differential is vacuous"
    assert got == expected
    assert _trees_equal(on.state, off.state)
    assert _trees_equal(on.faults, off.faults)
    assert on.config_id == off.config_id
    assert on.config_epoch == off.config_epoch
    # And the lanes saw the drive: rounds counted, alerts/decisions nonzero.
    on.sync()
    activity = on.activity
    assert activity["rounds"] == 10
    assert activity["alerts"] > 0
    assert activity["decisions_fast"] + activity["decisions_classic"] == len(
        expected[0]
    )
    assert off.activity is None  # telemetry=0: no lanes, no fetch, ever


def test_fused_convergence_bit_identical_and_lanes_path_independent():
    """``run_to_decision``/``run_until_membership`` (the fused while-loop
    drivers) decide identically with telemetry on; the lanes a fused drive
    accumulates equal a per-step drive's lanes exactly (path independence —
    the while-loop body IS the step body)."""
    off = _cluster(telemetry=False, seed=1)
    on = _cluster(telemetry=True, seed=1)
    stepped = _cluster(telemetry=True, seed=1)
    off.crash([2, 7]); on.crash([2, 7]); stepped.crash([2, 7])

    expected = off.run_to_decision(max_steps=32)
    got = on.run_to_decision(max_steps=32)
    assert got[0] == expected[0] and got[1] == expected[1]  # rounds, decided
    assert got[3] == expected[3]  # membership after the cut
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(expected[2]))
    assert _trees_equal(on.state, off.state)

    for _ in range(got[0]):
        stepped.step()
    assert _trees_equal(_lanes_host(on.telem), _lanes_host(stepped.telem))

    # The multi-cut wave: same resolution, same config chain, on vs off.
    off2 = _cluster(telemetry=False, seed=2)
    on2 = _cluster(telemetry=True, seed=2)
    for vc in (off2, on2):
        vc.crash([1, 4, 9])
    expected2 = off2.run_until_membership(21, max_steps=64, min_cuts=1)
    got2 = on2.run_until_membership(21, max_steps=64, min_cuts=1)
    assert got2 == expected2
    assert _trees_equal(on2.state, off2.state)
    assert on2.config_id == off2.config_id


def _fleet(telemetry, b=3, n=16, seed0=10):
    clusters = []
    for i in range(b):
        vc = VirtualCluster.create(
            n, k=3, h=3, l=1, cohorts=2, fd_threshold=2, seed=seed0 + i,
            telemetry=telemetry,
        )
        vc.assign_cohorts_roundrobin()
        # Tenant i loses i+1 members: every tenant resolves at a DIFFERENT
        # round, so the wave's coast-gating is genuinely exercised.
        vc.crash(list(range(1, 2 + i)))
        clusters.append(vc)
    return clusters


def test_fleet_wave_lanes_bit_identical_to_per_cluster_drives():
    """The fleet wave's coast-gating pin: tenants resolving at different
    rounds coast frozen — no phantom lane accumulation — so each tenant's
    lanes equal its own per-cluster ``run_until_membership`` drive, raw
    int32 for raw int32; and the wave itself matches the telemetry=0 wave."""
    singles = _fleet(telemetry=True)
    targets = [vc.membership_size - (1 + i) for i, vc in enumerate(singles)]
    expected = [
        vc.run_until_membership(t, max_steps=64, min_cuts=1)
        for vc, t in zip(singles, targets)
    ]
    assert all(r[2] for r in expected), "a tenant failed to resolve"

    fleet = TenantFleet.from_clusters(_fleet(telemetry=True))
    rounds, cuts, resolved, _ = fleet.run_until_membership(
        np.asarray(targets), max_steps=64, min_cuts=1
    )
    assert resolved.all()
    assert rounds.tolist() == [r[0] for r in expected]
    assert cuts.tolist() == [r[1] for r in expected]
    for t, vc in enumerate(singles):
        tenant_lanes = jax.tree_util.tree_map(
            lambda x, t=t: np.asarray(x)[t], fleet.telem
        )
        assert _trees_equal(tenant_lanes, _lanes_host(vc.telem)), t
    assert _trees_equal(
        fleet.state,
        jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *(vc.state for vc in singles)
        ),
    )

    # Same wave, telemetry off: the fleet results are unchanged.
    off = TenantFleet.from_clusters(_fleet(telemetry=False))
    rounds0, cuts0, resolved0, _ = off.run_until_membership(
        np.asarray(targets), max_steps=64, min_cuts=1
    )
    assert resolved0.all()
    assert rounds0.tolist() == rounds.tolist()
    assert cuts0.tolist() == cuts.tolist()
    assert _trees_equal(off.state, fleet.state)

    # The digest boundary agrees with the raw-lane comparison.
    fleet.sync()
    for t, vc in enumerate(singles):
        vc.sync()
        single_activity = vc.activity
        for field in TELEMETRY_DIGEST_FIELDS:
            assert fleet.tenant_activity[t][field] == single_activity[field]


def test_stream_drive_bit_identical_and_drain_is_the_fetch_boundary():
    """The streaming pipeline with telemetry on: bit-identical cuts/state to
    the telemetry=0 stream, and the drain — the pipeline's fetch seam — is
    where the activity cache refreshes (zero-minted before, measured
    after)."""
    waves = PoissonChurn(24, 40, rate=1.0, seed=7).waves(6)

    on = _cluster(telemetry=True, seed=0)
    assert on.activity["rounds"] == 0  # zero-minted at attach
    driver_on = StreamDriver(on, rounds_per_wave=4, depth=2)
    for wave in waves:
        driver_on.submit(wave)
    result_on = driver_on.drain()

    off = _cluster(telemetry=False, seed=0)
    driver_off = StreamDriver(off, rounds_per_wave=4, depth=2)
    for wave in waves:
        driver_off.submit(wave)
    result_off = driver_off.drain()

    assert result_on.cuts == result_off.cuts
    assert result_on.waves == result_off.waves == 6
    assert _trees_equal(on.state, off.state)
    assert _trees_equal(on.faults, off.faults)
    assert on.config_id == off.config_id

    activity = on.activity
    assert activity["rounds"] == result_on.rounds == 24
    assert activity["decisions_fast"] + activity["decisions_classic"] == (
        result_on.cuts
    )
    assert 0.0 < activity["active_fraction"] <= 1.0


def test_sharded_telem_wave_bit_identical_and_fleet_lanes_shard():
    """The lanes under a real device mesh: a ``VirtualCluster`` built on
    the mesh runs the telem wave (``sharded_program("wave", ..., carried=2)``)
    through ``run_until_membership`` and matches the single-device fused
    drive bit for bit — results AND lanes — and tenant-stacked lanes place
    onto the 3-D fleet mesh through the same rule table
    (``fleet_telemetry_shardings``: leading 'tenant' axis on every leaf,
    values unchanged by placement)."""
    from rapid_tpu.parallel.mesh import (
        TENANT_AXIS,
        fleet_telemetry_shardings,
        make_mesh,
        off_table,
        shard_pytree,
    )

    single = _cluster(telemetry=True, seed=6)
    single.crash([2, 7])
    r1, c1, resolved1, _ = single.run_until_membership(
        22, max_steps=64, min_cuts=1
    )
    assert resolved1

    mesh = make_mesh(jax.devices()[:8])
    vc = _cluster(telemetry=True, seed=6, mesh=mesh)
    vc.crash([2, 7])
    steps, cuts, resolved, _ = vc.run_until_membership(
        22, max_steps=64, min_cuts=1
    )
    assert resolved
    assert (steps, cuts) == (r1, c1)
    assert _trees_equal(vc.state, single.state)
    # One lane tells the two programs apart, and says so: a mesh's view
    # change gathers ``ring_alive`` whole at every commit, the one-device
    # program's flipped this cut's own positions.
    assert (int(vc.telem.tl_view_change_dense), int(single.telem.tl_view_change_dense)) == (cuts, 0)
    assert _trees_equal(
        _lanes_host(vc.telem._replace(tl_view_change_dense=single.telem.tl_view_change_dense)),
        _lanes_host(single.telem),
    )
    assert off_table(vc.telem, mesh) == () and off_table(vc.state, mesh) == ()

    # Tenant-stacked lanes on the ('tenant', 'cohort', 'nodes') mesh.
    singles = _fleet(telemetry=True, b=4)
    targets = [vc.membership_size - (1 + i) for i, vc in enumerate(singles)]
    fleet = TenantFleet.from_clusters(singles)
    _, _, resolved_f, _ = fleet.run_until_membership(
        np.asarray(targets), max_steps=64, min_cuts=1
    )
    assert resolved_f.all()
    shardings = fleet_telemetry_shardings(mesh3d := make_mesh(
        jax.devices()[:8], shape=(2, 2, 2)
    ))
    for leaf in jax.tree_util.tree_leaves(shardings):
        assert leaf.spec and leaf.spec[0] == TENANT_AXIS
    placed = shard_pytree(fleet.telem, shardings, mesh=mesh3d)
    assert _trees_equal(_lanes_host(placed), _lanes_host(fleet.telem))


def test_quiescent_soak_reads_exactly_zero_activity():
    """The zero-churn fact: an event-free soak counts its
    rounds and NOTHING else — any nonzero counter here is phantom
    activity."""
    vc = _cluster(telemetry=True, seed=5)
    for _ in range(16):
        vc.step()
    vc.sync()
    activity = vc.activity
    assert activity["rounds"] == 16
    for field in TELEMETRY_DIGEST_FIELDS:
        if field != "rounds":
            assert activity[field] == 0, field
    assert activity["rounds_undecided_hist"] == [0] * len(
        activity["rounds_undecided_hist"]
    )
    assert activity["active_fraction"] == 0.0
    assert activity["conflict_rate"] == 0.0


@pytest.mark.slow
def test_second_geometry_grid_bit_identical():
    """The wider on/off differential grid (second geometries: more slots,
    four cohorts, nonzero delivery spread, compact storage). Rides the
    unfiltered check.sh pass; tier-1 keeps the single-geometry
    representatives above as the acceptance pins."""
    for n, n_slots, cohorts, spread, compact, seed in [
        (48, 64, 4, 1, False, 3),
        (32, 48, 2, 0, True, 4),
    ]:
        def build(telemetry):
            vc = VirtualCluster.create(
                n, n_slots=n_slots, k=4, h=3, l=1, cohorts=cohorts,
                fd_threshold=2, delivery_spread=spread, compact=compact,
                seed=seed, telemetry=telemetry,
            )
            vc.assign_cohorts_roundrobin()
            return vc

        off, on = build(False), build(True)
        expected = _churn_drive(off, steps=14)
        got = _churn_drive(on, steps=14)
        assert expected[0], (n, "no cuts")
        assert got == expected, (n, n_slots, cohorts)
        assert _trees_equal(on.state, off.state), (n, n_slots, cohorts)
        assert on.config_id == off.config_id
