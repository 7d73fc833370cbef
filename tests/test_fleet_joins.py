"""Joins through the fleet's driver, and the whole-wave loop that stops.

``TenantFleet.inject_join_wave`` places ``(tenant, slot)`` joiners on the
STACKED state, device-side; the other side of every differential here is
``VirtualCluster.inject_join_wave`` on each tenant before stacking, leaf for
leaf. ``TenantFleet.run_until_membership`` dispatches ``fleet_wave_impl``, a
``while_loop`` over the gated step's round that ends when its slowest tenant
is done; the other side is the per-cluster nested loop. A whole bootstrap is
held against ``benchmarks/membership_model.py`` (numpy set arithmetic, no
engine code), and the ``paper-fleet-2k`` configuration file, cut to a small
size and nothing else, is driven through the benchmark's own target and
generator functions as the cell drives it (the jnp core on the CPU).
"""

import json
import os

import numpy as np
import pytest

import jax

from benchmarks import membership_model
from benchmarks.generators import bootstrap
from benchmarks.targets_fleet_join import FLEET_COUNTERS, JoinFleetTarget
from rapid_tpu.models.virtual_cluster import VirtualCluster
from rapid_tpu.tenancy.fleet import GATE_ROUND_COUNTERS, TenantFleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: 4 tenants of 16 members in 64 slots: different numbers of joiners, one
#: tenant with none.
JOINERS = (list(range(16, 40)), [], [20, 63, 41], list(range(30, 64)))
WAVE = dict(max_steps=64, max_cuts=4)


def cluster(seed: int, members: int = 16, slots: int = 64, spread: int = 2) -> VirtualCluster:
    vc = VirtualCluster.create(
        members, n_slots=slots, k=10, h=9, l=3, cohorts=4, fd_threshold=3,
        seed=seed, delivery_spread=spread,
    )
    vc.assign_cohorts_roundrobin()
    return vc


def pairs_of(joiners, shuffle_seed=None) -> np.ndarray:
    pairs = np.array(
        [(t, s) for t, slots in enumerate(joiners) for s in slots], dtype=np.int32
    ).reshape(-1, 2)
    if shuffle_seed is not None:  # the caller's order must not matter
        np.random.default_rng(shuffle_seed).shuffle(pairs)
    return pairs


def assert_tenants_equal(stacked, alone, label: str) -> None:
    """Every leaf of the stacked ``EngineState``, tenant by tenant, is the
    leaf of that tenant's own state: values and dtype."""
    for name in stacked._fields:
        for t, single in enumerate(alone):
            ours, theirs = np.asarray(getattr(stacked, name))[t], np.asarray(getattr(single, name))
            assert ours.dtype == theirs.dtype, (label, name, t)
            np.testing.assert_array_equal(ours, theirs, err_msg=f"{label}: {name}[{t}]")


@pytest.fixture(scope="module")
def joined():
    """The fleet after one ``inject_join_wave`` and the B clusters after
    their own, then both after ``run_until_membership``, observed at both
    points."""
    singles = [cluster(seed) for seed in range(4)]
    for vc, slots in zip(singles, JOINERS):
        if slots:
            vc.inject_join_wave(slots)
    fleet = TenantFleet.from_clusters([cluster(seed) for seed in range(4)])
    fleet.inject_join_wave(pairs_of(JOINERS, shuffle_seed=5))
    seen = {"fleet": fleet, "singles": singles}
    seen["injected"] = (
        jax.tree_util.tree_map(np.asarray, fleet.state),
        [jax.tree_util.tree_map(np.asarray, vc.state) for vc in singles],
    )
    targets = [16 + len(slots) for slots in JOINERS]
    min_cuts = [int(bool(slots)) for slots in JOINERS]
    seen["alone"] = [
        vc.run_until_membership(target, min_cuts=cuts, **WAVE)
        for vc, target, cuts in zip(singles, targets, min_cuts)
    ]
    seen["together"] = fleet.run_until_membership(targets, min_cuts=min_cuts, **WAVE)
    seen["targets"] = targets
    yield seen
    # Tier-1 runs near the process's limit of memory maps (the verify notes):
    # when the module is done, give back what it compiled.
    jax.clear_caches()


# -- (a) the join seam against B separate clusters ---------------------------


@pytest.mark.parametrize("leaf", [
    "join_pending", "obs_idx", "inval_obs", "fd_fired", "fire_round", "alive", "retired",
])
def test_injection_writes_what_the_clusters_method_writes(joined, leaf):
    stacked, alone = joined["injected"]
    for t, single in enumerate(alone):
        ours, theirs = getattr(stacked, leaf)[t], getattr(single, leaf)
        assert ours.dtype == theirs.dtype
        np.testing.assert_array_equal(ours, theirs, err_msg=f"{leaf}[{t}]")
    if leaf == "join_pending":  # and it wrote something: every named joiner is pending
        assert [int(row.sum()) for row in stacked.join_pending] == [len(s) for s in JOINERS]


def test_every_leaf_is_equal_after_injection(joined):
    assert_tenants_equal(*joined["injected"], "after inject_join_wave")


def test_every_leaf_is_equal_after_the_wave(joined):
    assert_tenants_equal(
        joined["fleet"].state, [vc.state for vc in joined["singles"]], "after run_until_membership")
    assert [vc.config_id for vc in joined["singles"]] == joined["fleet"].config_ids()


@pytest.mark.parametrize("observation", ["rounds", "cuts", "resolved", "sizes"])
def test_the_waves_fetch_is_the_per_cluster_loops(joined, observation):
    at = ("rounds", "cuts", "resolved", "sizes").index(observation)
    together = joined["together"][at]
    for t, alone in enumerate(joined["alone"]):
        if observation == "sizes":
            row = [size for size in together[t].tolist() if size >= 0]
            assert row == list(alone[at]) and len(row) == joined["together"][1][t]
        else:
            assert together[t] == alone[at], (observation, t)
    if observation == "resolved":
        assert together.all()
        assert joined["fleet"].membership_sizes().tolist() == joined["targets"]


def test_unchecked_injection_places_the_same_state():
    checked = TenantFleet.from_clusters([cluster(seed) for seed in range(4)])
    checked.inject_join_wave(pairs_of(JOINERS))
    unchecked = TenantFleet.from_clusters([cluster(seed) for seed in range(4)])
    unchecked.inject_join_wave(pairs_of(JOINERS), check_admissible=False)
    for ours, theirs in zip(*map(jax.tree_util.tree_leaves, (unchecked.state, checked.state))):
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    phases = unchecked.metrics.phase_timings["engine_dispatch"]
    assert "inject_join_place" in phases and "inject_join_admit" not in phases
    # the check fetched one bool a pair and nothing else
    assert checked.metrics.counters["engine_d2h_bytes"] == len(pairs_of(JOINERS))
    assert unchecked.metrics.counters.get("engine_d2h_bytes", 0) == 0


def test_an_injection_invalidates_the_carried_masks():
    fleet = TenantFleet.from_clusters([cluster(seed) for seed in range(2)])
    fleet.step()
    fleet.step()
    assert fleet.metrics.counters["engine_edge_mask_reuses"] == 1
    fleet.inject_join_wave([(0, 20), (1, 21)])
    fleet.step()  # obs_idx is a new array: CarriedMasks' identity rule rebuilds
    assert fleet.metrics.counters["engine_edge_mask_builds"] == 2
    fleet.inject_join_wave(np.zeros((0, 2), dtype=np.int32))  # nobody: nothing replaced
    fleet.step()
    assert fleet.metrics.counters["engine_edge_mask_builds"] == 2


# -- (b) a whole bootstrap against the plain reference -----------------------


@pytest.fixture(scope="module")
def bootstrapped():
    """3 tenants, 16 -> 256 in 4 waves of 60 joiners a tenant, no delivery
    jitter: the schedule is the benchmark generator's own draw."""
    config = {"tenants": 3, "members": 16, "slots": 256}
    fleet = TenantFleet.from_clusters(
        [cluster(seed, slots=256, spread=0) for seed in (11, 12, 13)])

    class Shape:  # what ``join_waves`` asks of a target
        tenants, members, slots = 3, 16, 256

    waves = bootstrap.join_waves({"waves": 4}, Shape, seed=4294967301)
    model = membership_model.MembershipModel(
        np.arange(256)[None, :].repeat(3, axis=0) < 16)
    epoch0, ids0 = fleet.config_epochs().copy(), fleet.config_ids()
    seen = {"fleet": fleet, "model": model, "waves": waves, "config": config, "trail": []}
    for join in waves:
        start = model.sizes()
        model.apply(bootstrap.NO_CRASH, join)
        fleet.inject_join_wave(join)
        rounds, cuts, resolved, sizes = fleet.run_until_membership(
            model.sizes(), max_steps=64, max_cuts=4, min_cuts=1)
        seen["trail"].append({
            "rounds": rounds, "tenant_cuts": cuts, "tenant_resolved": resolved,
            "sizes": sizes, "start": start, "goal": model.sizes(),
        })
    seen["epochs"] = fleet.config_epochs() - epoch0
    seen["ids"] = (ids0, fleet.config_ids())
    return seen


@pytest.mark.parametrize("wave", range(4))
def test_a_wave_lands_as_one_cut_and_grows_the_view(bootstrapped, wave):
    seen = bootstrapped["trail"][wave]
    assert seen["tenant_resolved"].all() and (seen["tenant_cuts"] == 1).all()
    assert (seen["sizes"][:, 0] == seen["goal"]).all() and (seen["sizes"][:, 1:] == -1).all()
    assert (seen["goal"] == seen["start"] + 60).all()  # strictly growing
    assert bootstrap.trail_faults(seen, seen["start"], seen["goal"]) == (0, 0)
    assert len(bootstrapped["waves"][wave]) == 3 * 60


def test_the_bootstrapped_view_is_the_plain_references(bootstrapped):
    fleet, model = bootstrapped["fleet"], bootstrapped["model"]
    numbers = model.compare_view(np.asarray(fleet.state.alive))
    assert numbers == dict.fromkeys(numbers, 0)
    assert model.sizes().tolist() == fleet.membership_sizes().tolist() == [256] * 3
    assert bootstrapped["epochs"].tolist() == [4] * 3  # Table 1: O(waves), never O(N)
    before, after = bootstrapped["ids"]
    assert all(a != b for a, b in zip(before, after))
    assert not np.asarray(fleet.state.join_pending).any()


@pytest.mark.parametrize("fault", ["short", "not_growing", "beyond_its_cuts"])
def test_the_trail_check_sees_a_wave_that_went_wrong(bootstrapped, fault):
    seen = dict(bootstrapped["trail"][1])
    sizes = seen["sizes"].copy()
    if fault == "short":
        sizes[2, 0] -= 1
        seen["tenant_resolved"] = np.array([True, True, False])
    elif fault == "not_growing":
        seen["tenant_cuts"] = np.array([1, 2, 1])
        sizes[1, :2] = (seen["goal"][1], seen["goal"][1])
    else:
        sizes[0, 2] = 999
    seen["sizes"] = sizes
    unresolved, unaccounted = bootstrap.trail_faults(seen, seen["start"], seen["goal"])
    assert unaccounted == 1 and unresolved == int(fault == "short")


# -- (c) the wave loop stops --------------------------------------------------


def test_the_loop_runs_the_rounds_of_its_slowest_tenant(joined):
    fleet = joined["fleet"]
    counters = fleet.metrics.counters
    slowest = max(rounds for rounds, *_ in joined["alone"])
    assert counters["engine_fleet_wave_rounds"] == slowest == joined["together"][0].max()
    assert slowest < WAVE["max_steps"]
    # one view change a tenant, all in the same round: the gate opened once
    assert counters["engine_fleet_commit_rounds"] == 1
    assert counters["engine_tenant_rounds"] == sum(r for r, *_ in joined["alone"])
    assert counters["engine_tenant_cuts"] == sum(bool(s) for s in JOINERS)
    tenancy = fleet.telemetry_snapshot()["engine"]["tenancy"]
    assert tenancy["fleet_wave_rounds_total"] == slowest


def test_a_fleet_with_no_event_runs_no_round():
    fleet = TenantFleet.from_clusters([cluster(seed) for seed in range(2)])
    before = jax.tree_util.tree_map(np.asarray, fleet.state)
    rounds, cuts, resolved, sizes = fleet.run_until_membership(16, min_cuts=0, **WAVE)
    assert rounds.tolist() == cuts.tolist() == [0, 0] and resolved.all() and (sizes == -1).all()
    counters = fleet.metrics.counters
    assert counters["engine_fleet_wave_rounds"] == 0
    assert all(counters[name] == 0 for name in GATE_ROUND_COUNTERS)
    for ours, theirs in zip(*map(jax.tree_util.tree_leaves, (fleet.state, before))):
        np.testing.assert_array_equal(np.asarray(ours), theirs)


def test_a_tenant_that_cannot_resolve_stops_the_loop_at_max_steps():
    fleet = TenantFleet.from_clusters([cluster(seed) for seed in range(2)])
    fleet.inject_join_wave([(0, 20), (0, 21), (1, 22)])
    # tenant 1 is asked for a membership its one joiner cannot give
    rounds, cuts, resolved, _ = fleet.run_until_membership(
        [18, 19], max_steps=12, max_cuts=4, min_cuts=1)
    assert resolved.tolist() == [True, False] and cuts.tolist() == [1, 1]
    assert rounds[1] == 12 and rounds[0] < 12
    assert fleet.metrics.counters["engine_fleet_wave_rounds"] == 12


# -- (d) rejections -----------------------------------------------------------


@pytest.mark.parametrize("pair", [(4, 20), (-1, 20), (0, 64), (0, -1)])
def test_a_pair_out_of_range_is_refused_on_the_host(pair):
    fleet = TenantFleet.from_clusters([cluster(seed) for seed in range(4)])
    with pytest.raises(IndexError, match="out of range"):
        fleet.inject_join_wave([(0, 20), pair])
    assert not np.asarray(fleet.state.join_pending).any()


@pytest.mark.parametrize("what", ["member", "pending", "retired"])
def test_an_inadmissible_joiner_is_refused_as_the_cluster_refuses_it(what):
    singles = [cluster(seed) for seed in range(2)]
    if what == "retired":  # tenant 1 evicts slot 3, whose identity is then spent
        singles[1].crash([3])
        assert singles[1].run_until_membership(15, max_steps=64, max_cuts=4, min_cuts=1)[2]
    fleet = TenantFleet.from_clusters(singles)
    bad = {"member": (1, 5), "pending": (1, 30), "retired": (1, 3)}[what]
    if what == "pending":
        fleet.inject_join_wave([(1, 30)])
    before = jax.tree_util.tree_map(np.asarray, fleet.state)
    with pytest.raises(ValueError, match="not admissible") as refused:
        fleet.inject_join_wave([(0, 40), bad, (1, 41)])
    assert str(list(bad)) in str(refused.value) and "[0, 40]" not in str(refused.value)
    # refused whole: nobody of the call was placed
    for ours, theirs in zip(*map(jax.tree_util.tree_leaves, (fleet.state, before))):
        np.testing.assert_array_equal(np.asarray(ours), theirs)


# -- (e) the configuration file's shape, small, through the new target --------


def held(*parts: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", *parts), encoding="utf-8") as handle:
        return json.load(handle)


SMALL = dict(held("configs", "paper-fleet-2k.json"), tenants=3, members=16, slots=80)
TRAFFIC = held("traffic", "bootstrap.json")


def test_the_configuration_is_the_papers_bootstrap():
    config = held("configs", "paper-fleet-2k.json")
    assert (config["tenants"], config["members"], config["slots"]) == (128, 64, 2000)
    assert (config["k"], config["h"], config["l"]) == (10, 9, 3)
    assert config["deployment"] == "fleet" and config["cohorts"] == 8
    assert (config["fd_threshold"], config["delivery_spread"]) == (3, 2)
    assert config["reduced"] == ["tenants"]
    assert config["assumed"] == ["tenants", "members", "cohorts", "fd_threshold", "delivery_spread"]
    assert "Fig. 5" in config["source"] and "Table 1" in config["source"] and len(config["source"]) <= 200
    assert len(config["guarantees"]) == 5
    # 8 equal waves of 242: one pair-array shape
    assert TRAFFIC["kind"] == "bootstrap" and TRAFFIC["waves"] == 8
    assert (config["slots"] - config["members"]) % TRAFFIC["waves"] == 0
    assert (config["slots"] - config["members"]) // TRAFFIC["waves"] == 242


@pytest.mark.parametrize("seed", [7, 4294967301])
def test_a_small_bootstrap_through_the_benchmarks_target(seed):
    target = JoinFleetTarget(SMALL, seed, "cpu")
    cfg = target.driver.cfg
    assert (target.kind, target.tenants, cfg.n, cfg.k, cfg.c) == ("fleet", 3, 80, 10, 8)
    assert (cfg.fd_threshold, cfg.delivery_spread) == (3, 2)
    assert np.asarray(target.driver.knobs.h).tolist() == [9] * 3
    assert np.asarray(target.driver.knobs.l).tolist() == [3] * 3
    waves = bootstrap.join_waves(TRAFFIC, target, seed)
    assert len(waves) == 8 and all(w.shape == (3 * 8, 2) for w in waves)
    named = np.concatenate(waves)
    for t in range(3):  # every spare slot of every tenant, once
        assert sorted(named[named[:, 0] == t, 1].tolist()) == list(range(16, 80))
    with pytest.raises(ValueError, match="joins only"):
        target.inject(np.array([[0, 1]], dtype=np.int32), waves[0])
    model = membership_model.MembershipModel(target.initial_alive())
    before, counted = target.view(), target.counters()
    cuts = np.zeros(3, dtype=np.int64)
    for join in waves:
        start = model.sizes()
        model.apply(bootstrap.NO_CRASH, join)
        target.inject(bootstrap.NO_CRASH, join)
        outcome = target.resolve(TRAFFIC["resolve"], model.sizes())
        assert outcome["resolved"] and outcome["sizes"].shape == (3, target.MAX_CUTS)
        assert bootstrap.trail_faults(outcome, start, model.sizes()) == (0, 0)
        assert 1 <= outcome["lockstep_rounds"] < target.MAX_STEPS
        cuts += outcome["tenant_cuts"]
    view = target.view()
    numbers = model.compare_view(view["alive"])
    numbers.update(model.compare_epochs(before, view))
    assert numbers == dict.fromkeys(numbers, 0)
    assert (view["epoch"] - before["epoch"] == cuts).all() and (cuts >= 8).all()
    after = target.counters()
    assert set(after["fleet"]) == set(FLEET_COUNTERS)
    assert after["fleet"]["engine_tenant_cuts"] - counted["fleet"].get("engine_tenant_cuts", 0) == cuts.sum()
    gate, rounds = (after["fleet"][name] for name in
                    ("engine_fleet_commit_rounds", "engine_fleet_wave_rounds"))
    assert 8 <= gate <= cuts.sum() and gate < rounds  # a view change in some rounds, not in all
    assert {"inject_join_admit", "inject_join_place", "fleet_wave"} <= set(after["dispatch_ms"])
