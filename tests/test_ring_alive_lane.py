"""``EngineState.ring_alive`` (liveness by ring position, PR 50) and the
observer tables a view change repairs (PR 52) on the live drivers: CPU twins of
the benchmark's cells' traffic through every driver that commits a view
change, each beside a twin whose programs trace the DENSE arm alone
(``dense_arms=True``: the whole ``alive[ring_perm]`` gather and the whole ring
walk at every commit, what every program did before and what a mesh's still
do). After every commit: ``ring_alive == alive[ring_perm]`` on both,
``inval_obs`` is the walk's table at every slot that is not a pending joiner
(the invariant the repair stands on), and the two states are equal lane for
lane; the telemetry plane's ``view_change_dense`` reads 0 over the cells'
traffic, 1 for the one cut that overflows the bucket by one member, and every
commit under the dense-only programs. Two states no cell sends ride along: a
leaver the next cut does not contain, and a joiner still pending after a cut.

The pure functions (the bounded updates against the gather and the walk at the
bucket's corners) are ``tests/test_ops_rings.py``'s, the structure of the
compiled programs ``tests/test_spans.py``'s.

The drives run in ONE process of their own (``python tests/test_ring_alive_lane.py``
prints one JSON record a driver): the executables they compile stay out of this
session, which ends within 1 % of vm.max_map_count."""

import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]

CLUSTER = dict(k=10, h=9, l=4, cohorts=8, fd_threshold=3, delivery_spread=2,
               concurrent_coordinators=2, telemetry=True)


def _snapshot(state):
    return {field: np.asarray(value) for field, value in state._asdict().items()}


def _lane_holds(shot) -> bool:
    """``ring_alive == alive[ring_perm]``, a tenant at a time under a fleet."""
    alive, perm, lane = shot["alive"], shot["ring_perm"], shot["ring_alive"]
    if alive.ndim == 1:
        alive, perm, lane = alive[None], perm[None], lane[None]
    return lane.dtype == np.bool_ and all(
        np.array_equal(lane[t], alive[t][perm[t]]) for t in range(alive.shape[0])
    )


def _table_holds(shot) -> bool:
    """``inval_obs`` is the ring walk's observer table of the membership at
    every slot that is not a pending joiner (a pending joiner's column holds
    its gatekeepers), a tenant at a time under a fleet."""
    from rapid_tpu.ops.rings import ring_topology_from_perm

    alive, perm, table, pending = (
        shot[field] for field in ("alive", "ring_perm", "inval_obs", "join_pending"))
    if alive.ndim == 1:
        alive, perm, table, pending = alive[None], perm[None], table[None], pending[None]
    return all(
        np.array_equal(
            table[t][:, ~pending[t]],
            np.asarray(ring_topology_from_perm(perm[t], alive[t]).obs_idx)[:, ~pending[t]])
        for t in range(alive.shape[0])
    )


def _commits(shots):
    """``(commits, members flipped)`` from one snapshot to the next, over
    the tenants; where a snapshot follows ONE commit the second is that
    cut's size."""
    out = []
    for before, after in zip(shots, shots[1:]):
        flipped = (before["alive"] ^ after["alive"]).reshape(-1, before["alive"].shape[-1])
        epochs = (after["config_epoch"] - before["config_epoch"]).reshape(-1)
        out += [(int(moved), int(row.sum())) for row, moved in zip(flipped, epochs) if moved]
    return out


def _dense_counts(driver):
    driver.sync()
    activity = getattr(driver, "tenant_activity", None) or [driver.activity]
    return sum(int(a["view_change_dense"]) for a in activity)


# -- the drivers: each returns (snapshots after every commit, dense commits) --


def cluster_wave(vcm, members=400, waves=((10, 10), (3, 6)), stagger=True, seed=50):
    """`cluster-100k.churn5`: crashes and joins in one wave, the whole-wave
    loop; one commit a call, so that every commit is looked at."""
    vc = vcm.VirtualCluster.create(members, n_slots=members + 40, seed=50, **CLUSTER)
    vc.assign_cohorts_roundrobin()
    if stagger:
        vc.stagger_fd_counts(np.random.default_rng(50), 3)
    standing, free, shots = list(range(members)), members, [_snapshot(vc.state)]
    for crashes, joins in waves:
        victims = np.random.default_rng(seed + crashes).choice(standing, size=crashes, replace=False)
        standing = sorted(set(standing) - set(victims.tolist()))
        vc.crash(victims)
        if joins:
            vc.inject_join_wave(list(range(free, free + joins)))
            standing, free = standing + list(range(free, free + joins)), free + joins
        for _ in range(4):
            if vc.membership_size == len(standing):
                break
            vc.run_until_membership(len(standing), max_steps=192, max_cuts=1, min_cuts=1)
            shots.append(_snapshot(vc.state))
        assert vc.membership_size == len(standing)
    return shots, _dense_counts(vc)


def cluster_wave_overflow(vcm):
    """No cell's: one member more than ``view_change_bucket(1,140)`` = 256
    crashes at once (23 %; detectors in step, so that they go in ONE cut),
    which takes the overflow arm; the cut after it fits again."""
    return cluster_wave(vcm, members=1100, waves=((257, 0), (4, 0)), stagger=False)


def _unheard(vc, senders):
    """Every cohort deaf to ``senders`` (none: healed)."""
    lane = np.zeros((vc.cfg.c, vc.cfg.n), dtype=bool)
    lane[:, list(senders)] = True
    vc.set_rx_block(lane)


def _observed_by(vc, watchers):
    """The slots with one of ``watchers`` among their ring observers."""
    return np.flatnonzero(np.isin(np.asarray(vc.state.inval_obs), list(watchers)).any(axis=0))


def cluster_leaver(vcm):
    """No cell's: a member announces its leave (``initiate_leave`` points its
    ``obs_idx`` column at itself) and nobody hears it, while six others crash:
    the cut that commits does NOT contain the leaver, and the view change has
    to put its column back onto the ring in ``obs_idx`` (only then do its
    real observers probe it, find it gone and evict it in the next cut)."""
    vc = vcm.VirtualCluster.create(400, n_slots=440, seed=50, **CLUSTER)
    vc.assign_cohorts_roundrobin()
    leaver, shots = 123, [_snapshot(vc.state)]
    standing = np.setdiff1d(np.arange(400), np.append(_observed_by(vc, [leaver]), leaver))
    victims = np.random.default_rng(52).choice(standing, size=6, replace=False)
    _unheard(vc, [leaver])
    vc.initiate_leave([leaver])
    assert (np.asarray(vc.state.obs_idx)[:, leaver] == leaver).all()
    vc.crash(victims)
    vc.run_until_membership(394, max_steps=192, max_cuts=1, min_cuts=1)
    shots.append(_snapshot(vc.state))
    assert vc.membership_size == 394 and shots[-1]["alive"][leaver]
    np.testing.assert_array_equal(  # back on the ring: the trap of a repair of obs_idx itself
        shots[-1]["obs_idx"][:, leaver], shots[-1]["inval_obs"][:, leaver])
    assert (shots[-1]["obs_idx"][:, leaver] != leaver).all()
    _unheard(vc, [])
    vc.run_until_membership(393, max_steps=192, max_cuts=1, min_cuts=1)
    shots.append(_snapshot(vc.state))
    assert vc.membership_size == 393 and not shots[-1]["alive"][leaver]
    return shots, _dense_counts(vc)


def cluster_pending_joiner(vcm):
    """No cell's: a joiner whose gatekeepers nobody hears stays pending while
    five members crash: the cut that commits does not admit it, and its column
    keeps the gatekeepers in both lanes (it sits in the LAST slot, which the
    compaction's filler entries name, so the repair does write -1 there and
    the commit's select puts the gatekeepers back). Healed, the next cut
    admits it."""
    vc = vcm.VirtualCluster.create(400, n_slots=440, seed=50, **CLUSTER)
    vc.assign_cohorts_roundrobin()
    joiner, shots = 439, [_snapshot(vc.state)]
    vc.inject_join_wave([joiner])
    gatekeepers = np.unique(np.asarray(vc.state.inval_obs)[:, joiner])
    assert (gatekeepers >= 0).all()
    standing = np.setdiff1d(np.arange(400), np.append(_observed_by(vc, gatekeepers), gatekeepers))
    victims = np.random.default_rng(53).choice(standing, size=5, replace=False)
    _unheard(vc, gatekeepers)
    vc.crash(victims)
    vc.run_until_membership(395, max_steps=192, max_cuts=1, min_cuts=1)
    shots.append(_snapshot(vc.state))
    assert vc.membership_size == 395 and shots[-1]["join_pending"][joiner]
    for lane in ("obs_idx", "inval_obs"):
        assert np.isin(shots[-1][lane][:, joiner], gatekeepers).all()
    _unheard(vc, [])
    vc.run_until_membership(396, max_steps=192, max_cuts=1, min_cuts=1)
    shots.append(_snapshot(vc.state))
    assert vc.membership_size == 396 and shots[-1]["alive"][joiner]
    return shots, _dense_counts(vc)


def stream_step(vcm):
    """`cluster-100k.trickle`: Poisson joins and crashes streamed through the
    carried step, drained after every wave."""
    from rapid_tpu.serving import PoissonChurn, StreamDriver

    vc = vcm.VirtualCluster.create(300, n_slots=380, seed=46, **CLUSTER)
    vc.assign_cohorts_roundrobin()
    vc.stagger_fd_counts(np.random.default_rng(46), 3)
    source = PoissonChurn(300, 380, rate=6.0, seed=50)
    driver, shots = StreamDriver(vc, rounds_per_wave=8, depth=2), [_snapshot(vc.state)]
    for wave in source.waves(6):
        driver.submit(wave)
        driver.drain()
        shots.append(_snapshot(vc.state))
    return shots, _dense_counts(vc)


def _fleet(fleetm, tenants, members, slots):
    return fleetm.TenantFleet.create(
        tenants, members, n_slots=slots, k=10, cohorts=4,
        knobs=[(9, 3, 2), (8, 3, 2), (9, 4, 2)][:tenants], delivery_spread=2,
        telemetry=True,
    )


def fleet_wave(vcm):
    """`paper-fleet-2k.bootstrap`: join waves through the fleet's whole-wave
    loop, tenants of different wave sizes, one of them losing a member too."""
    from rapid_tpu.tenancy import fleet as fleetm

    fleet = _fleet(fleetm, 3, 16, 64)
    slots, members, shots = np.full(3, 16), np.full(3, 16), [_snapshot(fleet.state)]
    for wave, widths in enumerate(np.asarray([(8, 5, 0), (12, 12, 3), (20, 6, 9)])):
        fleet.inject_join_wave([
            (t, int(slots[t]) + j) for t, width in enumerate(widths) for j in range(width)
        ])
        slots, members, moved = slots + widths, members + widths, widths > 0
        if wave == 1:  # a leave beside the joins, in one tenant
            fleet.faults = fleet.faults._replace(crashed=fleet.faults.crashed.at[0, 3].set(True))
            members[0] -= 1
        _, _, resolved, _ = fleet.run_until_membership(
            members, max_cuts=4, min_cuts=moved.astype(np.int32))
        assert resolved.all(), (wave, resolved)
        shots.append(_snapshot(fleet.state))
    return shots, _dense_counts(fleet)


def fleet_decision(vcm):
    """`paper-fleet-1k.crash10` / `paper-grid-1k.crashF`: concurrent crashes,
    another count a tenant, every tenant to its decision in one dispatch."""
    import jax.numpy as jnp

    from rapid_tpu.tenancy import fleet as fleetm

    fleet = _fleet(fleetm, 3, 200, 200)
    crashed = np.zeros((3, 200), dtype=bool)
    for t, count in enumerate((10, 2, 16)):
        crashed[t, np.random.default_rng(t).choice(200, size=count, replace=False)] = True
    fleet.faults = fleet.faults._replace(crashed=jnp.asarray(crashed))
    shots = [_snapshot(fleet.state)]
    _, decided, _, _ = fleet.run_to_decision(max_steps=64)
    assert decided.all()
    shots.append(_snapshot(fleet.state))
    return shots, _dense_counts(fleet)


def mesh_cluster(vcm, mesh=None):
    """`cluster-10m.crash1` on virtual devices: the driver's three verbs."""
    vc = vcm.VirtualCluster.create(400, n_slots=416, seed=50, mesh=mesh, **CLUSTER)
    vc.assign_cohorts_roundrobin()
    shots = [_snapshot(vc.state)]
    vc.crash(np.random.default_rng(7).choice(400, size=8, replace=False))
    assert vc.run_to_decision(max_steps=64)[1]
    shots.append(_snapshot(vc.state))
    vc.inject_join_wave(list(range(400, 408)))
    vc.crash([int(np.flatnonzero(shots[-1]["alive"])[5])])
    vc.run_until_membership(399, max_steps=192, max_cuts=1, min_cuts=1)
    shots.append(_snapshot(vc.state))
    if vc.membership_size != 399:
        vc.run_until_membership(399, max_steps=192, max_cuts=1, min_cuts=1)
        shots.append(_snapshot(vc.state))
    vc.crash([int(np.flatnonzero(shots[-1]["alive"])[9])])
    for _ in range(24):
        if bool(vc.step().decided):
            break
    shots.append(_snapshot(vc.state))
    assert vc.membership_size == 398
    return shots, _dense_counts(vc)


DRIVERS = {
    "cluster_wave": cluster_wave,
    "cluster_wave_overflow": cluster_wave_overflow,
    "cluster_leaver": cluster_leaver,
    "cluster_pending_joiner": cluster_pending_joiner,
    "stream_step": stream_step,
    "fleet_wave": fleet_wave,
    "fleet_decision": fleet_decision,
    "mesh_1d": mesh_cluster,
    "mesh_2d": mesh_cluster,
}


def _dense_only_programs(vcm, fleetm):
    """The drivers' tables with every program tracing the dense arm alone."""
    import jax
    import jax.numpy as jnp

    def carried_step(cfg, state, *rest):
        *observers, faults, _masks = rest
        new_state, *out = vcm.engine_step_impl(cfg, state, *observers, faults, dense_arms=True)
        return (new_state, *out, vcm._edge_masks(cfg, new_state, faults))

    def fleet_decision(cfg, state, *rest):
        *observers, faults, knobs, max_steps = rest

        def one(state, *rest):
            *observers, faults, kn = rest
            return vcm.run_to_decision_impl(
                fleetm._tenant_cfg(cfg, kn), state, *observers, faults, max_steps,
                dense_arms=True)

        return (*jax.vmap(one)(state, *observers, faults, knobs), jnp.zeros((2,), jnp.int32))

    def fleet_wave(cfg, state, *rest):
        return (*fleetm.fleet_wave_lockstep_impl(cfg, state, *rest),
                jnp.zeros((len(fleetm.WAVE_LOOP_COUNTERS),), jnp.int32))

    return {
        "step": vcm.jit_per_observer_count(carried_step, donated=(3,)),
        "decision": vcm.jit_per_observer_count(
            functools.partial(vcm.run_to_decision_impl, dense_arms=True)),
        "wave": vcm.jit_per_observer_count(
            functools.partial(vcm.run_until_membership_impl, dense_arms=True), static=(5,)),
    }, {
        "decision": vcm.jit_per_observer_count(fleet_decision),
        "wave": vcm.jit_per_observer_count(fleet_wave, static=(6,)),
    }


def drive() -> None:
    """Every driver through its own programs and through the dense-only twins
    of them (a mesh's ARE dense-only: its twin is the one-device cluster);
    one JSON record a driver on stdout."""
    import jax

    from rapid_tpu.models import virtual_cluster as vcm
    from rapid_tpu.parallel.mesh import make_mesh
    from rapid_tpu.tenancy import fleet as fleetm

    own = dict(vcm._ROUND_PROGRAMS), dict(fleetm._FLEET_PROGRAMS)
    dense = _dense_only_programs(vcm, fleetm)
    meshes = {"1d": lambda: make_mesh(jax.devices()[:8]),
              "2d": lambda: make_mesh(jax.devices()[:8], shape=(2, 4))}

    def run(name, programs, mesh=None):
        vcm._ROUND_PROGRAMS.update(programs[0])
        fleetm._FLEET_PROGRAMS.update(programs[1])
        try:
            return DRIVERS[name](vcm, mesh) if name.startswith("mesh") else DRIVERS[name](vcm)
        finally:
            vcm._ROUND_PROGRAMS.update(own[0])
            fleetm._FLEET_PROGRAMS.update(own[1])

    from rapid_tpu.ops.rings import view_change_bucket

    for name in DRIVERS:
        if name.startswith("mesh"):
            # ours: the one-device cluster; theirs: the same verbs on the mesh
            ours, theirs = run(name, own), run(name, own, meshes[name[-2:]]())
        else:
            ours, theirs = run(name, own), run(name, dense)
        commits = _commits(ours[0])
        bucket = view_change_bucket(ours[0][0]["alive"].shape[-1])
        print(json.dumps({
            "driver": name,
            "commits": sum(moved for moved, _ in commits),
            "largest_cut": max(size for _, size in commits),
            "cuts_over_the_bucket": sum(moved == 1 and size > bucket for moved, size in commits),
            "bucket": bucket,
            "same_commits": len(ours[0]) == len(theirs[0]) and commits == _commits(theirs[0]),
            "lane_holds": all(_lane_holds(shot) for shot in (*ours[0], *theirs[0])),
            "table_holds": all(_table_holds(shot) for shot in (*ours[0], *theirs[0])),
            "leaves_that_differ": sorted({
                field for left, right in zip(ours[0], theirs[0]) for field in left
                if left[field].dtype != right[field].dtype
                or not np.array_equal(left[field], right[field])
            }),
            "dense_commits": ours[1],
            "dense_commits_of_the_dense_programs": theirs[1],
        }), flush=True)
        jax.clear_caches()


@pytest.fixture(scope="module")
def drives():
    done = subprocess.run(
        [sys.executable, str(Path(__file__).resolve())], capture_output=True, text=True,
        cwd=str(REPO), timeout=1200,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO),
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    )
    assert done.returncode == 0, done.stderr[-3000:]
    records = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
    return {record["driver"]: record for record in records}


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_a_driver_keeps_the_lane_exact_and_its_state_the_dense_arms(drives, driver):
    record = drives[driver]
    assert record["commits"] >= (1 if driver == "fleet_decision" else 2) and record["same_commits"]
    # liveness by ring position IS alive[ring_perm] after every commit, under
    # the update and under the gather alike, and every lane of the state is
    # the one the dense arm gives
    assert record["lane_holds"]
    # what the repair of the observer table stands on: inval_obs is the
    # walk's table wherever no joiner is pending
    assert record["table_holds"]
    assert record["leaves_that_differ"] == []
    # which arm ran, by the telemetry plane: the dense-only programs (and a
    # mesh's) count every commit ...
    assert record["dense_commits_of_the_dense_programs"] == record["commits"]
    if driver == "cluster_wave_overflow":  # ... ours the one cut of bucket + 1 members
        assert record["largest_cut"] == record["bucket"] + 1
        assert record["dense_commits"] == record["cuts_over_the_bucket"] == 1 < record["commits"]
    else:  # ... and no cut of a cell's traffic comes near the bucket
        assert record["largest_cut"] <= record["bucket"] // 2
        assert record["dense_commits"] == record["cuts_over_the_bucket"] == 0


if __name__ == "__main__":
    drive()
