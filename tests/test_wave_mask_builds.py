"""The whole-wave loops build their per-edge masks where a round will read
them: differential and count.

``run_until_membership_impl`` builds at the head of every convergence and
``fleet_wave_impl`` before its loop and at the head of a round that follows a
commit, never in the cut's arm, so the commit a wave ends with builds
nothing. That moves WHEN ``_edge_masks`` runs, never what a round computes:
a wave that lands in several cuts is held here, leaf for leaf and lane for
lane, against the same wave driven round by round with ``step`` (whose
program keeps the build in the cut's arm and hands the masks to its driver),
and the fleet loop's own count of its builds (``engine_edge_mask_builds``)
against the rounds in which its gate opened. The cluster's loop has no
run-time gate to count: ``tests/test_spans.py`` pins its one build a
convergence by structure.
"""

import jax
import numpy as np
import pytest

from rapid_tpu.models.virtual_cluster import VirtualCluster
from rapid_tpu.tenancy.fleet import TenantFleet

GEOMETRY = dict(n_slots=72, cohorts=16, fd_threshold=2, delivery_spread=1, telemetry=True, trace=64)
WAVE = dict(max_steps=64, max_cuts=4)
#: What a tenant is given before the wave, and the membership that resolves
#: it. Staggered detection pushes the crash cut BEHIND the join cut, so
#: ``both`` lands in two cuts; alone, the joins cut early and the crashes late.
TARGETS = {"both": 70, "join": 72, "crash": 58, "none": 60}
OBSERVED = ("state", "telem", "trace_ring")


def tenant(kind: str, seed: int = 11) -> VirtualCluster:
    vc = VirtualCluster.create(60, seed=seed, **GEOMETRY)
    vc.assign_cohorts_roundrobin()
    if kind in ("both", "crash"):
        vc.crash([7, 31])
        vc.stagger_fd_counts(np.random.default_rng(5), spread_rounds=8)
    if kind in ("both", "join"):
        vc.inject_join_wave(list(range(60, 72)))
    return vc


def fleet_of(*kinds: str) -> TenantFleet:
    return TenantFleet.from_clusters([tenant(kind, seed=11 + t) for t, kind in enumerate(kinds)])


def run_wave(fleet: TenantFleet, *kinds: str):
    return fleet.run_until_membership(
        [TARGETS[kind] for kind in kinds], min_cuts=[int(kind != "none") for kind in kinds], **WAVE)


def snapshot(driver, tenant_at=None) -> dict:
    """The driver's state and both observers as host arrays (one tenant's
    slice of a fleet's)."""
    pick = (lambda x: np.asarray(x)) if tenant_at is None else (lambda x: np.asarray(x)[tenant_at])
    return {name: jax.tree_util.tree_map(pick, getattr(driver, name)) for name in OBSERVED}


def stepped(driver, targets, min_cuts, tenants=None) -> list:
    """The wave driven round by round with ``step``: per tenant (one for a
    cluster) ``(rounds, cuts, resolved, sizes, snapshot)``, the snapshot
    taken in the round in which the whole-wave loop would freeze the tenant
    (resolved, or out of cuts), as the loops' ``done`` lane decides it."""
    count = 1 if tenants is None else tenants
    cuts, sizes, out = [0] * count, [[] for _ in range(count)], [None] * count

    def freeze(t, rounds, resolved):
        out[t] = (rounds, cuts[t], resolved, tuple(sizes[t]), snapshot(driver, None if tenants is None else t))

    now = np.atleast_1d(np.asarray(driver.state.n_members))
    for t in range(count):
        if now[t] == targets[t] and min_cuts[t] <= 0:
            freeze(t, 0, True)
    for rounds in range(1, WAVE["max_steps"] + 1):
        if None not in out:
            break
        decided = np.atleast_1d(np.asarray(driver.step().decided))
        now = np.atleast_1d(np.asarray(driver.state.n_members))
        for t in range(count):
            if out[t] is not None or not decided[t]:
                continue
            cuts[t] += 1
            sizes[t].append(int(now[t]))
            resolved = bool(now[t] == targets[t] and cuts[t] >= min_cuts[t])
            if resolved or cuts[t] >= WAVE["max_cuts"]:
                freeze(t, rounds, resolved)
    return out


@pytest.fixture(scope="module")
def waves():
    """A cluster whose wave lands in two cuts, and a fleet whose tenants cut
    in different rounds (one of them twice, one not at all): each through
    its whole-wave loop and, a twin, round by round."""
    seen = {}
    fused = tenant("both")
    rounds, cuts, resolved, sizes = fused.run_until_membership(TARGETS["both"], min_cuts=1, **WAVE)
    seen["cluster"] = {
        "wave": [(rounds, cuts, resolved, sizes, snapshot(fused))],
        "stepped": stepped(tenant("both"), [TARGETS["both"]], [1]),
    }
    kinds = ("both", "join", "crash", "none")
    fleet = fleet_of(*kinds)
    rounds, cuts, resolved, sizes = run_wave(fleet, *kinds)
    seen["fleet"] = {
        "wave": [
            (int(rounds[t]), int(cuts[t]), bool(resolved[t]),
             tuple(size for size in sizes[t].tolist() if size >= 0), snapshot(fleet, t))
            for t in range(len(kinds))
        ],
        "stepped": stepped(
            fleet_of(*kinds), [TARGETS[kind] for kind in kinds],
            [int(kind != "none") for kind in kinds], tenants=len(kinds)),
        "driver": fleet,
    }
    yield seen
    # Tier-1 runs near the process's limit of memory maps (the verify notes):
    # when the module is done, give back what it compiled.
    jax.clear_caches()


# -- (b) a wave of several cuts is the same wave driven round by round -------


@pytest.mark.parametrize("driver", ["cluster", "fleet"])
def test_the_scenario_lands_in_several_cuts(waves, driver):
    cuts = [cuts for _, cuts, *_ in waves[driver]["wave"]]
    assert max(cuts) >= 2
    if driver == "fleet":  # and its tenants are done in different rounds, one in none
        rounds = [rounds for rounds, *_ in waves[driver]["wave"]]
        assert len(set(rounds)) == len(rounds) and min(rounds) == 0


@pytest.mark.parametrize("observation", ["rounds", "cuts", "resolved", "sizes"])
@pytest.mark.parametrize("driver", ["cluster", "fleet"])
def test_the_waves_fetch_is_the_stepped_waves(waves, driver, observation):
    at = ("rounds", "cuts", "resolved", "sizes").index(observation)
    ours = [seen[at] for seen in waves[driver]["wave"]]
    assert ours == [seen[at] for seen in waves[driver]["stepped"]]
    if observation == "resolved":
        assert all(ours)


@pytest.mark.parametrize("observed", OBSERVED)
@pytest.mark.parametrize("driver", ["cluster", "fleet"])
def test_every_leaf_is_the_stepped_waves(waves, driver, observed):
    for t, (wave, twin) in enumerate(zip(waves[driver]["wave"], waves[driver]["stepped"])):
        ours, theirs = wave[-1][observed], twin[-1][observed]
        for name in ours._fields:
            one, other = getattr(ours, name), getattr(theirs, name)
            assert one.dtype == other.dtype, (observed, name, t)
            np.testing.assert_array_equal(one, other, err_msg=f"{observed}.{name}[{t}]")


# -- (c) the fleet loop's count of its builds --------------------------------


def test_a_wave_of_g_gate_rounds_that_ends_on_one_builds_g_minus_one(waves):
    counters = waves["fleet"]["driver"].metrics.counters
    gate_rounds = counters["engine_fleet_commit_rounds"]
    assert gate_rounds >= 3  # the joins' cut, the second cut of ``both``, the crashes'
    # the slowest tenant resolved with its cut: the loop ended on a gate round
    assert counters["engine_fleet_wave_rounds"] == max(r for r, *_ in waves["fleet"]["wave"])
    assert counters["engine_edge_mask_builds"] == gate_rounds - 1
    assert "engine_edge_mask_reuses" not in counters  # the step's driver's, not the loop's


def test_scrape_and_snapshot_follow_the_loops_builds_by_name(waves):
    fleet = waves["fleet"]["driver"]
    builds = fleet.metrics.counters["engine_edge_mask_builds"]
    assert builds >= 2
    assert fleet.telemetry_snapshot()["metrics"]["engine_edge_mask_builds"] == builds
    scraped = [line for line in fleet.prometheus_text().splitlines()
               if line.startswith("rapid_engine_edge_mask_builds_total")]
    assert len(scraped) == 1 and scraped[0].endswith(f" {builds}")


@pytest.mark.parametrize("kinds, gate_rounds", [
    (("join",) * 4, 1),  # every tenant lands its one cut in the same round
    (("none",) * 4, 0),  # resolved at entry: no round
])
def test_a_wave_that_ends_with_its_only_cut_or_runs_no_round_builds_nothing(kinds, gate_rounds):
    fleet = fleet_of(*kinds)
    _, cuts, resolved, _ = run_wave(fleet, *kinds)
    assert resolved.all() and cuts.tolist() == [int(kind != "none") for kind in kinds]
    counters = fleet.metrics.counters
    assert counters["engine_fleet_commit_rounds"] == gate_rounds
    assert counters["engine_edge_mask_builds"] == 0


def test_a_wave_that_stalls_after_a_cut_builds_once_for_every_gate_round():
    # the one case in which the loop builds as often as it did with the build
    # in the cut's arm: the rounds after the last cut read the rebuild
    fleet = fleet_of("join", "join", "join", "join")
    _, cuts, resolved, _ = fleet.run_until_membership(
        [72, 72, 72, 71], min_cuts=1, max_steps=12, max_cuts=4)  # the last: a size its joiners pass over
    assert resolved.tolist() == [True, True, True, False] and cuts.tolist() == [1] * 4
    counters = fleet.metrics.counters
    assert counters["engine_fleet_wave_rounds"] == 12
    assert counters["engine_edge_mask_builds"] == counters["engine_fleet_commit_rounds"] == 1
