"""The meshless per-round step carries its per-edge masks (ISSUE 28).

The bar is bit-identity with the parent's programs: a driver that carries
``(observer_active, blocked_rows)`` from round to round, rebuilding them only
when its inputs changed under it and in a cut's taken arm, must leave every
leaf of the state, the events and the observers' lanes equal, after every
round, to what ``engine_step`` / ``fleet_step_impl`` (which build the masks
in every round) leave on the same inputs. The schedules below cross every way
the four leaves the masks are built from can change; the counters say how
often the mechanism engaged.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rapid_tpu.models.virtual_cluster import VirtualCluster, engine_step_impl
from rapid_tpu.serving.stream import FleetWave, StreamDriver, StreamWave
from rapid_tpu.tenancy.fleet import TenantFleet, _tenant_cfg, fleet_step_impl

OBSERVERS = {
    "plain": {},
    "telem": {"telemetry": True},
    "trace": {"telemetry": True, "trace": 8},
}


def _cluster(observers="plain", n=28, n_slots=40, seed=5):
    vc = VirtualCluster.create(
        n, n_slots=n_slots, k=3, h=3, l=1, cohorts=2, fd_threshold=2,
        delivery_spread=1, seed=seed, **OBSERVERS[observers],
    )
    vc.assign_cohorts_roundrobin()
    return vc


def _rx_block(n_slots, slots):
    block = np.zeros((2, n_slots), dtype=bool)
    block[1, slots] = True  # cohort 1 cannot hear these observers
    return block


def _fleet(observers="plain"):
    """Three tenants that differ before stacking in every way a single
    cluster's injection seams can make them differ."""
    clusters = [_cluster(observers, n=20, n_slots=28, seed=10 + i) for i in range(3)]
    clusters[0].inject_join_wave([22, 23])
    clusters[1].initiate_leave([4])
    clusters[2].set_rx_block(_rx_block(28, [0, 1, 2]))
    return TenantFleet.from_clusters(clusters)


@jax.jit
def _clone(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


# -- the parent's step, driven through the same driver object -----------------

# ``engine_step_impl`` (the mesh's step, the masks built inside) jitted once
# per observer count, the state and the observers donated.
_PARENT_STEP = tuple(
    jax.jit(engine_step_impl, static_argnums=(0,), donate_argnums=tuple(range(1, 2 + k)))
    for k in range(3)
)


def _parent_fleet_step_impl(cfg, knobs, faults, state, *observers):
    if not observers:
        return fleet_step_impl(cfg, state, faults, knobs)

    def one(kn, f, s, *obs):
        return engine_step_impl(_tenant_cfg(cfg, kn), s, *obs, f)

    return jax.vmap(one)(knobs, faults, state, *observers)


_parent_fleet_step = jax.jit(_parent_fleet_step_impl, static_argnums=(0,))


def _parent_step(driver):
    """One round as the parent's driver dispatched it: the program that
    builds the masks inside, on the pytrees ``driver`` holds."""
    carried = tuple(
        tree for tree in (driver.state, driver.telem, driver.trace_ring)
        if tree is not None
    )
    if isinstance(driver, TenantFleet):
        out = _parent_fleet_step(driver.cfg, driver.knobs, driver.faults, *carried)
    else:
        out = _PARENT_STEP[len(carried) - 1](driver.cfg, *carried, driver.faults)
    driver.state = out[0]
    if driver.telem is not None:
        driver.telem = out[1]
    if driver.trace_ring is not None:
        driver.trace_ring = out[2]
    return out[-1]


def _assert_same(ours, theirs, what):
    ours, theirs = jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)
    assert len(ours) == len(theirs), what
    for index, (got, want) in enumerate(zip(ours, theirs)):
        assert got.dtype == want.dtype, (what, index)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=f"{what} leaf {index}")


def _rounds_in_lockstep(carried, parent, rounds, label):
    """``rounds`` rounds on both drivers, every leaf compared after each;
    returns which of them committed a cut (any tenant's, for a fleet)."""
    cuts = []
    for r in range(rounds):
        events, want = carried.step(), _parent_step(parent)
        where = f"{label}, round {r}"
        _assert_same(events, want, f"events after {where}")
        _assert_same(
            (carried.state, carried.telem, carried.trace_ring),
            (parent.state, parent.telem, parent.trace_ring),
            f"carried pytrees after {where}",
        )
        cuts.append(bool(np.asarray(events.decided).any()))
    return cuts


def _on_both(drivers, verb, *args, **kwargs):
    return [getattr(driver, verb)(*args, **kwargs) for driver in drivers]


def _crashed_set(driver, index, value):
    """A revive as recovery and the chaos harness spell a fault change: a new
    ``FaultInputs`` assigned from outside the class."""
    driver.faults = driver.faults._replace(
        crashed=driver.faults.crashed.at[index].set(value)
    )


def _cluster_schedule(carried, parent):
    """Rows of (label, the change, rounds stepped after it, whether the
    driver has to build before the first of them): everything but
    ``set_flaky_edges`` leaves a new array in one of the four leaves."""
    both = (carried, parent)
    flaky = np.zeros((40, 3), dtype=bool)
    flaky[20, 1] = True
    return [
        ("crash", lambda: _on_both(both, "crash", [3]), 4, True),
        ("crash then revive", lambda: _on_both(both, "crash", [5]), 1, True),
        ("revive", lambda: _on_both(both, "revive", [5]), 2, True),
        ("join wave", lambda: _on_both(both, "inject_join_wave", [30, 31]), 4, True),
        ("leave", lambda: _on_both(both, "initiate_leave", [7]), 4, True),
        ("set_rx_block", lambda: _on_both(both, "set_rx_block", _rx_block(40, [0, 1])), 1, True),
        ("crash under a block", lambda: _on_both(both, "crash", [9]), 5, True),
        ("crash before a fused loop", lambda: _on_both(both, "crash", [11]), 1, True),
        ("run_to_decision", lambda: _on_both(both, "run_to_decision", 32), 2, True),
        ("two crashes", lambda: _on_both(both, "crash", [12, 13]), 4, True),
        # last: one failing probe leaves its subject between L and H, which
        # holds every later proposal back
        ("set_flaky_edges", lambda: _on_both(both, "set_flaky_edges", flaky), 2, False),
    ]


def _fleet_schedule(carried, parent):
    both = (carried, parent)
    return [
        ("stacked join wave, leave and block", lambda: None, 4, True),
        ("stream_crash", lambda: _on_both(both, "stream_crash", [(0, 3), (2, 5)]), 4, True),
        ("crash then revive", lambda: _on_both(both, "stream_crash", [(1, 6)]), 1, True),
        ("revive by assignment", lambda: [_crashed_set(d, (1, 6), False) for d in both], 2, True),
        ("crash before a fused loop", lambda: _on_both(both, "stream_crash", [(2, 7)]), 1, True),
        ("run_to_decision", lambda: _on_both(both, "run_to_decision", 32), 2, True),
        ("quarantine", lambda: _on_both(both, "quarantine", [1]), 2, False),
        ("crash in every tenant", lambda: _on_both(both, "stream_crash", [(0, 8), (1, 9), (2, 10)]), 4, True),
    ]


@pytest.mark.parametrize("observers", sorted(OBSERVERS))
@pytest.mark.parametrize("kind", ["cluster", "fleet"])
def test_carried_masks_leave_every_leaf_the_parents_step_leaves(kind, observers):
    make, schedule = {
        "cluster": (_cluster, _cluster_schedule), "fleet": (_fleet, _fleet_schedule),
    }[kind]
    carried, parent = make(observers), make(observers)
    builds = cuts_inside_a_wave = rounds = 0
    for label, change, after, stale in schedule(carried, parent):
        change()
        committed = _rounds_in_lockstep(carried, parent, after, label)
        rounds += after
        builds += stale
        # a cut that is not its wave's last round: the rounds after it run
        # on the masks the taken arm built
        cuts_inside_a_wave += any(committed[:-1])
    counters = carried.metrics.counters
    assert counters["engine_edge_mask_builds"] == builds
    assert counters["engine_edge_mask_reuses"] == rounds - builds
    assert cuts_inside_a_wave >= 3
    assert "engine_edge_mask_builds" not in parent.metrics.counters


@pytest.mark.parametrize("kind", ["cluster", "fleet"])
@pytest.mark.parametrize("seam", ["restore", "faults"])
def test_an_assignment_from_outside_the_class_is_not_stepped_on_old_masks(kind, seam):
    """``benchmarks/targets.py::restore`` assigns ``driver.state`` and
    ``driver.faults``; recovery assigns ``target.faults``. Neither knows of
    the carried masks, and neither has to."""
    make = {"cluster": _cluster, "fleet": _fleet}[kind]
    crash = {
        "cluster": lambda d, slot: d.crash([slot]),
        "fleet": lambda d, slot: d.stream_crash([(0, slot), (2, slot)]),
    }[kind]
    carried, parent = make(), make()
    pristine = [(_clone(d.state), d.faults) for d in (carried, parent)]
    for driver in (carried, parent):
        crash(driver, 3)
    assert any(_rounds_in_lockstep(carried, parent, 4, "before the assignment"))
    builds = carried.metrics.counters["engine_edge_mask_builds"]
    for driver, (state, faults) in zip((carried, parent), pristine):
        if seam == "restore":
            driver.state, driver.faults = _clone(state), faults
        else:
            slot = (0, 6) if kind == "fleet" else 6
            _crashed_set(driver, slot, True)
    _rounds_in_lockstep(carried, parent, 1, f"first round after the {seam}")
    assert carried.metrics.counters["engine_edge_mask_builds"] == builds + 1
    for driver in (carried, parent):
        crash(driver, 5)
    assert any(_rounds_in_lockstep(carried, parent, 4, f"after the {seam}"))
    assert carried.metrics.counters["engine_edge_mask_builds"] == builds + 2


@pytest.mark.parametrize("kind", ["cluster", "fleet"])
def test_a_wave_of_eight_rounds_builds_once_and_reuses_seven_times(kind):
    """Three streamed waves with one injection each: three builds dispatched
    by the driver, twenty-one steps on carried masks, and not a byte fetched
    while the waves are submitted (the counters are host-side increments)."""
    if kind == "cluster":
        target = _cluster()
        waves = [StreamWave(crash=(3,)), StreamWave(join=(30,)), StreamWave(crash=(5,), join=(31,))]
    else:
        target = _fleet()
        waves = [FleetWave(crash=pairs) for pairs in (((0, 3), (2, 5)), ((1, 6),), ((0, 7), (2, 8)))]
    driver = StreamDriver(target, rounds_per_wave=8, depth=4)
    counters = target.metrics.counters
    d2h = counters["engine_d2h_bytes"]
    for wave in waves:
        driver.submit(wave)
    assert counters["engine_edge_mask_builds"] == 3
    assert counters["engine_edge_mask_reuses"] == 21
    assert counters["engine_d2h_bytes"] == d2h
    result = driver.drain()
    assert result.cuts >= 3  # the in-arm builds: one a cut, counted by the cuts

    # seams that leave the four leaves alone cost no build
    if kind == "cluster":
        flaky = np.zeros((40, 3), dtype=bool)
        flaky[21, 2] = True
        target.set_flaky_edges(flaky)
        target.step()
        target._stamp_fired_edges(jnp.asarray([22], dtype=jnp.int32), np.ones((1, 3), dtype=bool))
        target.step()
        target.stagger_fd_counts(np.random.default_rng(0), 2)
        target.assign_cohorts_roundrobin()
    else:
        target.quarantine([2])
        target.sync()
    target.step()
    # the drain's own reads built nothing either: every step since the last
    # wave's injection ran on carried masks
    assert counters["engine_edge_mask_builds"] == 3
    snapshot = target.telemetry_snapshot()["metrics"]
    assert snapshot["engine_edge_mask_builds"] == 3
    assert snapshot["engine_edge_mask_reuses"] == counters["engine_edge_mask_reuses"] > 21


def test_a_meshed_cluster_keeps_the_parents_step_and_counts_nothing():
    from rapid_tpu.parallel.mesh import make_mesh

    vc = VirtualCluster.create(
        28, n_slots=32, k=3, h=3, l=1, cohorts=2, fd_threshold=2,
        mesh=make_mesh(jax.devices()[:2], shape=(1, 2)),
    )
    vc.assign_cohorts_roundrobin()
    vc.crash([3])
    for _ in range(3):
        vc.step()
    assert "engine_edge_mask_builds" not in vc.metrics.counters
    assert "engine_edge_mask_reuses" not in vc.metrics.counters
    names = vc.prometheus_text()
    assert "rapid_engine_edge_mask_builds_total" in names  # zero-filled all the same
