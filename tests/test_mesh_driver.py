"""A ``VirtualCluster`` built on a device mesh, through the driver's own verbs.

The meshed cluster must be the one-device cluster in every observation: the
same alive mask, configuration id, round count, cut count and cut sizes for a
crash-only ``run_to_decision`` and for a crash+join ``run_until_membership``,
on the ``('cohort','nodes')`` shapes (2,2), (1,4) and (4,1) of four of the
eight virtual CPU devices. ``benchmarks/membership_model.py`` (numpy set
arithmetic, no engine code) has to agree with both. After every verb every
leaf lies where ``PARTITION_RULES`` puts it, a second cluster of the same
shape compiles nothing, and slots that do not divide the mesh raise
``ShardingShapeError`` by name.
"""

import numpy as np
import pytest

import jax

from benchmarks import membership_model
from rapid_tpu.models.virtual_cluster import VirtualCluster
from rapid_tpu.ops import rings
from rapid_tpu.parallel.mesh import (
    COHORT_AXIS,
    NODE_AXIS,
    ShardingShapeError,
    make_mesh,
    off_table,
    state_shardings,
)
from rapid_tpu.utils import engine_telemetry

MEMBERS, SLOTS, COHORTS = 2000, 2200, 8
N_CRASH, N_JOIN = 20, 100
SHAPES = [(2, 2), (1, 4), (4, 1)]


def mesh_of(shape):
    return make_mesh(jax.devices()[:4], shape=shape)


def build(mesh, seed=3, **kw):
    vc = VirtualCluster.create(
        MEMBERS, n_slots=SLOTS, cohorts=COHORTS, fd_threshold=3, seed=seed,
        delivery_spread=2, mesh=mesh, **kw,
    )
    vc.assign_cohorts_roundrobin()
    return vc


def schedule(seed):
    rng = np.random.default_rng(seed)
    first = rng.choice(MEMBERS, size=N_CRASH, replace=False)
    rest = np.setdiff1d(np.arange(MEMBERS), first)
    second = rng.choice(rest, size=N_CRASH, replace=False)
    return first, second, np.arange(MEMBERS, MEMBERS + N_JOIN)


def drive(vc, seed=5):
    """Crash-only ``run_to_decision``, then crash+join
    ``run_until_membership``; every observation the comparison reads."""
    first, second, joiners = schedule(seed)
    vc.crash(first)
    vc.sync()
    rounds, decided, _, members = vc.run_to_decision(64)
    crash_only = {
        "rounds": rounds, "decided": decided, "members": members,
        "alive": vc.alive_mask.copy(), "config_id": vc.config_id,
        "epoch": vc.config_epoch,
    }
    vc.crash(second)
    vc.inject_join_wave(joiners)
    vc.sync()
    target = MEMBERS - 2 * N_CRASH + N_JOIN
    rounds, cuts, resolved, sizes = vc.run_until_membership(
        target, max_steps=192, max_cuts=4, min_cuts=1
    )
    churn = {
        "rounds": rounds, "cuts": cuts, "resolved": resolved, "sizes": sizes,
        "alive": vc.alive_mask.copy(), "config_id": vc.config_id,
        "epoch": vc.config_epoch,
    }
    return crash_only, churn


def same(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(
        (a[k] == b[k]).all() if isinstance(a[k], np.ndarray) else a[k] == b[k]
        for k in a
    )


@pytest.fixture(scope="module")
def one_device():
    return drive(build(None))


@pytest.fixture(scope="module")
def meshed():
    """One driven cluster per mesh shape, shared by the tests that read it."""
    out = {}
    for shape in SHAPES:
        vc = build(mesh_of(shape))
        out[shape] = (vc, drive(vc))
    return out


@pytest.mark.parametrize("shape", SHAPES)
def test_meshed_cluster_is_the_one_device_cluster(one_device, meshed, shape):
    crash_only, churn = meshed[shape][1]
    assert crash_only["decided"] and churn["resolved"]
    assert same(crash_only, one_device[0])
    assert same(churn, one_device[1])


@pytest.mark.parametrize("where", ["one_device", *SHAPES])
def test_membership_model_agrees(one_device, meshed, where):
    crash_only, churn = one_device if where == "one_device" else meshed[where][1]
    first, second, joiners = schedule(5)
    initial = np.zeros((1, SLOTS), dtype=bool)
    initial[0, :MEMBERS] = True
    pairs = lambda slots: np.stack([np.zeros_like(slots), slots], axis=1)
    model = membership_model.MembershipModel(initial)
    model.apply(pairs(first), np.zeros((0, 2), dtype=int))
    assert membership_model.failures(model.compare_view(crash_only["alive"][None])) == 0
    assert crash_only["members"] == model.sizes()[0]
    model.apply(pairs(second), pairs(joiners))
    assert membership_model.failures(model.compare_view(churn["alive"][None])) == 0
    assert churn["sizes"][-1] == model.sizes()[0]
    assert 1 <= churn["epoch"] - crash_only["epoch"] <= 2 and crash_only["epoch"] == 1


def on_the_table(vc) -> bool:
    trees = [t for t in (vc.state, vc.faults, vc.telem, vc.trace_ring) if t is not None]
    return all(off_table(tree, vc.mesh) == () for tree in trees)


@pytest.mark.parametrize("shape", SHAPES)
def test_every_leaf_keeps_the_rule_tables_sharding_after_every_verb(shape):
    mesh = mesh_of(shape)
    vc = build(mesh, telemetry=True, trace=8)
    verbs = [
        ("create", lambda: None),
        ("stagger_fd_counts", lambda: vc.stagger_fd_counts(np.random.default_rng(1), 2)),
        ("crash", lambda: vc.crash([3, 77, 130])),
        ("sync", vc.sync),
        ("step", vc.step),
        ("run_to_decision", lambda: vc.run_to_decision(32)),
        ("inject_join_wave", lambda: vc.inject_join_wave(np.arange(MEMBERS, MEMBERS + 10))),
        ("run_until_membership", lambda: vc.run_until_membership(MEMBERS + 7, max_cuts=2)),
        ("initiate_leave", lambda: vc.initiate_leave([9, 10])),
        ("set_rx_block", lambda: vc.set_rx_block(np.zeros((COHORTS, SLOTS), dtype=bool))),
        ("set_flaky_edges", lambda: vc.set_flaky_edges(np.zeros((SLOTS, 10), dtype=bool))),
        ("revive", lambda: vc.revive([3])),
        ("run_until_converged", lambda: vc.run_until_converged(16)),
    ]
    for name, verb in verbs:
        verb()
        assert on_the_table(vc), name
    assert vc.metrics.counters["engine_sharding_drift"] == 0
    assert vc.metrics.counters["engine_state_devices"] == 4
    # a [k,n] leaf is cut along its slots, never whole on a device
    nodes = mesh.shape[NODE_AXIS]
    for leaf in (vc.state.ring_perm, vc.state.obs_idx, vc.state.key_hi):
        assert {s.data.shape for s in leaf.addressable_shards} == {(10, SLOTS // nodes)}
    cohort = mesh.shape[COHORT_AXIS]
    assert {s.data.shape for s in vc.state.report_bits.addressable_shards} == {
        (COHORTS // cohort, SLOTS // nodes)
    }


def test_the_drift_counter_moves_when_a_leaf_leaves_the_table():
    mesh = mesh_of((2, 2))
    vc = build(mesh)
    assert vc.metrics.counters["engine_sharding_drift"] == 0
    whole = jax.device_put(
        np.asarray(vc.state.ring_perm), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    )
    vc.state = vc.state._replace(ring_perm=whole)
    assert off_table(vc.state, mesh) == ("ring_perm",)
    vc.crash([1])
    assert vc.metrics.counters["engine_sharding_drift"] == 1
    # the programs state their input shardings: a leaf off the table is
    # refused, not resharded in silence
    with pytest.raises(ValueError, match="does not match the sharding"):
        vc.run_to_decision(32)


@pytest.mark.parametrize("shape", SHAPES)
def test_a_second_cluster_of_the_same_shape_compiles_nothing(meshed, shape):
    assert meshed[shape]  # the first cluster of this shape has been driven
    with engine_telemetry.CompileDelta() as window:
        drive(build(mesh_of(shape), seed=4), seed=6)
    assert window.delta["compiles"] == 0


def test_slots_or_cohorts_that_do_not_divide_the_mesh_raise_by_name():
    mesh = mesh_of((2, 2))
    with pytest.raises(ShardingShapeError, match="dimension 1 .= 2201. does not divide mesh axis nodes"):
        VirtualCluster.create(MEMBERS, n_slots=2201, cohorts=COHORTS, mesh=mesh)
    with pytest.raises(ShardingShapeError, match="does not divide mesh axis cohort"):
        VirtualCluster.create(MEMBERS, n_slots=SLOTS, cohorts=3, mesh=mesh)
    # left to the driver, the slots are padded to the least multiple that divides
    vc = VirtualCluster.create(2001, cohorts=COHORTS, mesh=mesh_of((1, 4)))
    assert vc.cfg.n == 2004 and vc.membership_size == 2001


def test_the_mosaic_kernel_is_refused_under_a_mesh():
    with pytest.raises(ValueError, match="use_pallas is off under a mesh"):
        VirtualCluster.create(MEMBERS, n_slots=SLOTS, use_pallas=True, mesh=mesh_of((2, 2)))


@pytest.mark.parametrize("shape", SHAPES)
def test_the_transfer_counter_counts_each_shard_of_a_fetch_once(meshed, shape):
    vc = meshed[shape][0]
    moved = lambda arr: sum(s.data.nbytes for s in arr.addressable_shards if s.replica_id == 0)
    before = vc.metrics.counters["engine_d2h_bytes"]
    mask = vc.alive_mask
    assert vc.metrics.counters["engine_d2h_bytes"] - before == moved(vc.state.alive) == mask.nbytes
    before = vc.metrics.counters["engine_d2h_bytes"]
    assert vc.config_epoch >= 1
    assert vc.metrics.counters["engine_d2h_bytes"] - before == moved(vc.state.config_epoch) == 4


def test_an_existing_state_is_adopted_onto_the_mesh(one_device):
    mesh = mesh_of((2, 2))
    vc = build(None)
    moved = VirtualCluster(vc.cfg, vc.state, mesh=mesh)
    assert off_table(moved.state, mesh) == () and off_table(moved.faults, mesh) == ()
    assert same(drive(moved)[0], one_device[0])
    for leaf, want in zip(moved.state, state_shardings(mesh)):
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim)


def _ring_case(name):
    """(perm [5, 600], alive [600]) for one corner of the aliveness range."""
    rng = np.random.default_rng(2)
    perm = np.stack([rng.permutation(600) for _ in range(5)]).astype(np.int32)
    alive = {
        "four_fifths_alive": rng.random(600) < 0.8,
        "int16_perm": rng.random(600) < 0.8,
        "all_alive": np.ones(600, dtype=bool),
        "one_alive": np.arange(600) == 311,
        "none_alive": np.zeros(600, dtype=bool),
        # the first and the last slot of ring 0's key order
        "ends_of_a_ring": np.isin(np.arange(600), perm[0, [0, -1]]),
    }[name]
    # int16 is the compact engine's index width
    return perm.astype(np.int16 if name == "int16_perm" else np.int32), alive


@pytest.mark.parametrize("case", [
    "four_fifths_alive", "all_alive", "one_alive", "none_alive", "ends_of_a_ring", "int16_perm",
])
def test_rings_one_at_a_time_give_the_batched_topology(monkeypatch, case):
    perm, alive = _ring_case(case)
    assert perm.shape[-1] < rings.RING_AT_A_TIME_SLOTS
    batched = rings.ring_topology_from_perm(perm, alive)
    monkeypatch.setattr(rings, "RING_AT_A_TIME_SLOTS", 512)
    one_at_a_time = rings.ring_topology_from_perm(perm, alive)
    for a, b in zip(batched, one_at_a_time):
        assert a.dtype == b.dtype == np.int32 and (np.asarray(a) == np.asarray(b)).all()
    if case in ("one_alive", "none_alive"):  # under two alive nobody observes anybody
        assert (np.asarray(one_at_a_time.obs_idx) == -1).all()
        assert (np.asarray(one_at_a_time.subj_idx) == -1).all()
    if case == "ends_of_a_ring":  # the two wrap round to each other on every ring
        first, last = int(perm[0, 0]), int(perm[0, -1])
        assert (np.asarray(one_at_a_time.obs_idx)[:, first] == last).all()
        assert (np.asarray(one_at_a_time.subj_idx)[:, last] == first).all()
