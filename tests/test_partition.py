"""A one-way partition as traffic, and the consensus path under a counter.

``VirtualCluster.set_partition(cohorts, senders)`` makes the named receiver
cohorts deaf to the named slots by an index scatter on the device, and from
the first call on the cluster counts which arm of its consensus decided
(``engine_classic_rounds`` / ``engine_classic_decisions`` /
``engine_fast_decisions``). The system is held against
``benchmarks/consensus_model.py`` (the fast quorum, the coordinator rule and
the cut detectors' tallies as counting in numpy, no code shared with the
engine) and, where the telemetry plane is on, against its ``decisions_*``:

(a) the seam: ``set_partition`` is ``set_rx_block`` of the dense lane bit for
    bit (state, faults, the re-stamp after a heal, the carried masks), one
    upload, no fetch, bounds checked on the host;
(b) the path: the benchmark cell's geometry at a 5,000-member twin, fewer deaf
    cohorts, the boundary the fast quorum sets, a true conflict of values, a
    partition healed before the timer fires;
(c) a cluster that never sets a partition is the cluster it was: no counts in
    its programs, its fetches or its scrape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import consensus_model
from rapid_tpu.models import virtual_cluster as vcm
from rapid_tpu.models.state import FaultInputs, initial_state
from rapid_tpu.models.virtual_cluster import VirtualCluster
from rapid_tpu.utils.dispatch import ENGINE_DISPATCH_PHASES
from rapid_tpu.utils.exposition import CONSENSUS_PATH_COUNTERS

#: The cell's shapes cut twenty times: 5,000 members in 5,125 slots, 125
#: crashes and a rack of 250, 64 round-robin cohorts of which 24 are deaf.
MEMBERS, SLOTS, COHORTS, CRASHES, RACK = 5000, 5125, 64, 125, 250
FD_THRESHOLD, SPREAD, FALLBACK = 3, 2, 8


@pytest.fixture(scope="module")
def compiled():
    """Every test of the module takes this: tier-1 runs near the process's
    limit of memory maps, so the module gives back what it compiled."""
    yield True
    jax.clear_caches()


def twin(stagger=0, telemetry=True, members=MEMBERS, slots=SLOTS):
    vc = VirtualCluster.create(
        members, n_slots=slots, k=10, h=9, l=4, cohorts=COHORTS, fd_threshold=FD_THRESHOLD,
        delivery_spread=SPREAD, concurrent_coordinators=2, fallback_rounds=FALLBACK,
        telemetry=telemetry, seed=3)
    vc.assign_cohorts_roundrobin()
    if stagger:
        vc.stagger_fd_counts(np.random.default_rng(5), stagger)
    vc.sync()
    return vc


def mask(indices, size) -> np.ndarray:
    out = np.zeros(size, dtype=bool)
    out[np.asarray(indices, dtype=np.int64)] = True
    return out


def expected(vc, crash, deaf, unheard, announce_round=None) -> dict:
    """The plain reference's verdict on one schedule of ``vc``."""
    cfg = vc.cfg
    return consensus_model.outcome(
        members=int(vc.state.n_members), alive=np.asarray(vc.state.alive),
        cohort_of=np.asarray(vc.state.cohort_of), crashed=mask(crash, cfg.n),
        deaf=mask(deaf, cfg.c), unheard=mask(unheard, cfg.n),
        observers=np.asarray(vc.state.obs_idx), high=cfg.h, low=cfg.l,
        fallback_rounds=cfg.fallback_rounds, announce_round=announce_round)


def paths(vc) -> tuple:
    """(classic rounds, classic decisions, fast decisions) as the driver counts them."""
    return tuple(int(vc.metrics.counters[name]) for name in CONSENSUS_PATH_COUNTERS)


def draw(seed=7):
    order = np.random.default_rng(seed).permutation(MEMBERS)
    return np.sort(order[:CRASHES]), np.sort(order[CRASHES:CRASHES + RACK])


def resolve(vc, crash, deaf, unheard):
    """One step as the benchmark's cell makes it; returns the wave's outcome."""
    vc.set_partition(deaf, unheard)
    vc.crash(crash)
    vc.sync()
    outcome = vc.run_until_membership(MEMBERS - len(crash), max_steps=192, max_cuts=4, min_cuts=1)
    vc.sync()  # the telemetry plane's digest comes with a sync
    return outcome


# -- (a) the seam --------------------------------------------------------------


def small(seed=2):
    vc = VirtualCluster.create(
        60, n_slots=64, k=10, h=9, l=4, cohorts=6, fd_threshold=1, delivery_spread=2, seed=seed)
    vc.assign_cohorts_roundrobin()
    return vc


def dense(vc, cohorts, senders) -> np.ndarray:
    lane = np.zeros((vc.cfg.c, vc.cfg.n), dtype=bool)
    lane[np.ix_(np.asarray(cohorts, dtype=np.int64), np.asarray(senders, dtype=np.int64))] = True
    return lane


def same_leaves(a, b) -> None:
    for name, left, right in zip(a._fields, a, b):
        assert left.dtype == right.dtype and left.shape == right.shape, name
        assert np.array_equal(np.asarray(left), np.asarray(right)), name


@pytest.mark.parametrize("moment", ["at rest", "with fired edges", "healed mid-configuration"])
def test_set_partition_is_set_rx_block_of_the_dense_lane(compiled, moment):
    by_index, by_lane = small(), small()
    cohorts, senders = [1, 4], [3, 9, 17, 40, 41]
    if moment != "at rest":
        for vc in (by_index, by_lane):
            vc.crash([5, 6])
            vc.step()  # the edges fire in round 0; the spread holds the decision back
    by_index.set_partition(cohorts, senders)
    by_lane.set_rx_block(dense(by_lane, cohorts, senders))
    if moment == "healed mid-configuration":
        for vc in (by_index, by_lane):
            assert not bool(vc.step().decided)
        by_index.set_partition([], [])
        by_lane.set_rx_block(np.zeros((6, 64), dtype=bool))
    same_leaves(by_index.state, by_lane.state)
    same_leaves(by_index.faults, by_lane.faults)
    if moment != "at rest":
        fired = np.asarray(by_index.state.fd_fired)
        stamped = np.asarray(by_index.state.fire_round)[fired]
        assert fired.any() and (stamped == int(by_index.state.round_idx)).all()
    # and the rounds that follow agree too, counts or none
    for vc in (by_index, by_lane):
        vc.step()
    same_leaves(by_index.state, by_lane.state)


def test_a_partition_leaves_the_carried_masks_stale(compiled):
    vc = small()
    vc.step()
    vc.step()
    counters = vc.metrics.counters
    builds, reuses = counters["engine_edge_mask_builds"], counters["engine_edge_mask_reuses"]
    vc.set_partition([2], [7, 8])
    vc.step()
    assert counters["engine_edge_mask_builds"] == builds + 1
    vc.step()
    assert counters["engine_edge_mask_reuses"] == reuses + 1


def test_the_setter_uploads_once_and_fetches_nothing(compiled):
    """At the cell's real size: 24 cohorts and 5,000 senders cross as 20 KB
    where the dense lane is 6.56 MB."""
    vc = VirtualCluster.create(100_000, n_slots=102_500, k=10, cohorts=COHORTS, seed=1)
    uploads = []
    account = vc._account_h2d
    vc._account_h2d = lambda *arrays: (uploads.append(arrays), account(*arrays))[1]
    before = dict(vc.metrics.counters)
    vc.set_partition(np.arange(24), np.arange(0, 100_000, 20))
    vc.sync()
    after = vc.metrics.counters
    assert len(uploads) == 1 and len(uploads[0]) == 1
    assert after["engine_h2d_bytes"] - before["engine_h2d_bytes"] == (24 + 5000) * 4 == 20_096
    assert after["engine_d2h_bytes"] - before.get("engine_d2h_bytes", 0) == 4  # the sync's checksum
    lane = np.asarray(vc.faults.rx_block)
    assert lane.shape == (64, 102_500) and lane.nbytes == 6_560_000
    assert lane.sum() == 24 * 5000 and lane[:24, ::20][:, :5000].all() and not lane[24:].any()
    phases = vc.metrics.phase_timings["engine_dispatch"]
    assert phases["inject_partition"].count == 1 and "inject_partition" in ENGINE_DISPATCH_PHASES
    vc.set_rx_block(lane)  # the dense seam runs under the same phase, and uploads the lane
    assert phases["inject_partition"].count == 2
    assert after["engine_h2d_bytes"] - before["engine_h2d_bytes"] == 20_096 + 6_560_000


def test_the_setter_checks_its_arguments_on_the_host(compiled):
    vc = small()
    with pytest.raises(IndexError, match="cohort indices out of range"):
        vc.set_partition([6], [1])
    with pytest.raises(IndexError, match="slot indices out of range"):
        vc.set_partition([1], [64])
    assert vc.paths is None and not np.asarray(vc.faults.rx_block).any()
    vc.mesh = object()  # a cluster on a mesh refuses before it touches it
    with pytest.raises(ValueError, match="off under a mesh"):
        vc.set_partition([1], [2])


# -- (b) the path --------------------------------------------------------------


def test_the_cells_geometry_decides_once_by_the_classic_round(compiled):
    """24 of 64 cohorts deaf to a rack of 5 %, detectors staggered over three
    rounds as the cell's: 15 rounds, one cut of exactly the crashed set."""
    vc = twin(stagger=3)
    crash, rack = draw()
    want = expected(vc, crash, np.arange(24), rack)
    assert (want["path"], want["cuts"], want["attempts"]) == ("classic", 1, 1)
    assert want["votes"] < want["quorum"] == MEMBERS - (MEMBERS - 1) // 4
    rounds, cuts, resolved, sizes = resolve(vc, crash, np.arange(24), rack)
    assert (rounds, cuts, resolved, sizes) == (15, 1, True, (MEMBERS - CRASHES,))
    assert paths(vc) == (1, 1, 0)
    assert (vc.activity["decisions_classic"], vc.activity["decisions_fast"]) == (1, 0)
    alive = np.asarray(vc.state.alive)
    assert not alive[crash].any() and alive[rack].all() and alive.sum() == MEMBERS - CRASHES
    scrape = vc.prometheus_text()
    for name, value in zip(CONSENSUS_PATH_COUNTERS, (1, 1, 0)):
        assert f'rapid_{name}_total{{node="virtual-cluster/{SLOTS}"}} {value}' in scrape


@functools.lru_cache(maxsize=None)
def boundary() -> int:
    """The fewest deaf cohorts that cost this draw its fast quorum, by the
    plain reference alone (one twin and one sweep for the four cases)."""
    vc = twin()
    crash, rack = draw()
    for deaf in range(1, COHORTS):
        if expected(vc, crash, np.arange(deaf), rack)["path"] == "classic":
            return deaf
    raise AssertionError("no number of deaf cohorts loses the quorum")


@pytest.mark.parametrize("deaf", ["8", "24", "last fast", "first classic"])
def test_the_engine_takes_the_path_and_the_round_the_reference_gives(compiled, deaf):
    """No stagger: every detector fires in round ``fd_threshold`` and every
    report is delivered ``delivery_spread`` rounds later at the latest, so the
    hearing cohorts announce in round 5 and the reference gives the round."""
    edge = boundary()
    assert 12 <= edge <= 18  # 16 of 64 leave three quarters of the voters: the quorum's share
    deaf = {"8": 8, "24": 24, "last fast": edge - 1, "first classic": edge}[deaf]
    vc = twin()
    crash, rack = draw()
    want = expected(vc, crash, np.arange(deaf), rack, announce_round=FD_THRESHOLD + SPREAD)
    assert want["path"] == ("classic" if deaf >= edge else "fast") and want["cuts"] == 1
    assert (want["votes"] >= want["quorum"]) == (want["path"] == "fast")
    rounds, cuts, resolved, sizes = resolve(vc, crash, np.arange(deaf), rack)
    assert (cuts, resolved, sizes) == (1, True, (MEMBERS - CRASHES,))
    assert rounds == want["round"] == (5 if want["path"] == "fast" else 5 + FALLBACK - 1)
    classic = int(want["path"] == "classic")
    assert paths(vc) == (classic, classic, 1 - classic)
    assert (vc.activity["decisions_classic"], vc.activity["decisions_fast"]) == (classic, 1 - classic)
    assert np.array_equal(~np.asarray(vc.state.alive)[:MEMBERS], want["cut"][:MEMBERS])


@pytest.mark.parametrize("deaf", [
    pytest.param(np.arange(24), id="zones 0-2"),  # cohort 0, the first of the largest, is deaf
    pytest.param(np.arange(40, 64), id="zones 5-7"),
])
def test_a_true_conflict_commits_the_majoritys_cut_in_one(compiled, deaf):
    """The deaf cohorts hear nothing of half the victims and announce the
    other half: two values, no fast quorum, and the coordinator rule picks the
    cut most of its quorum voted, never the subset first. Which cohorts are
    deaf must not matter: the rule counts values (``Paxos.java:287-308``),
    where a count per cohort let the first of the largest cohorts win (two
    cuts for one on the parent when that cohort is deaf)."""
    vc = twin()
    observers = np.asarray(vc.state.obs_idx)
    rng = np.random.default_rng(11)
    while True:  # victims whose observers are all distinct, healthy members
        victims = rng.choice(MEMBERS, 8, replace=False)
        watchers = observers[:, victims]
        if len(np.unique(watchers)) == watchers.size and not np.isin(watchers, victims).any():
            break
    unheard = np.unique(observers[:, victims[:4]])
    want = expected(vc, victims, deaf, unheard, announce_round=FD_THRESHOLD + SPREAD)
    assert (want["path"], want["cuts"], want["attempts"]) == ("classic", 1, 1)
    assert 0 < want["votes"] < want["quorum"] and want["cut"].sum() == 8
    vc.set_partition(deaf, unheard)
    vc.crash(victims)
    vc.sync()
    subset = np.asarray(vc.state.alive).copy()
    rounds, cuts, resolved, sizes = vc.run_until_membership(MEMBERS - 8, 192, 4, 1)
    assert (rounds, cuts, resolved, sizes) == (want["round"], 1, True, (MEMBERS - 8,))
    assert paths(vc) == (1, 1, 0)
    assert np.array_equal(subset & ~np.asarray(vc.state.alive), want["cut"])


def test_a_partition_healed_before_the_timer_fires_decides_fast(compiled):
    vc = twin()
    crash, rack = draw()
    vc.set_partition(np.arange(24), rack)
    vc.crash(crash)
    for _ in range(FD_THRESHOLD + SPREAD + 3):  # three rounds into the recovery delay
        assert not bool(vc.step().decided)
    assert 0 < int(vc.state.rounds_undecided) < FALLBACK
    vc.set_partition([], [])
    rounds, cuts, resolved, sizes = vc.run_until_membership(MEMBERS - CRASHES, 192, 4, 1)
    vc.sync()
    assert (cuts, resolved, sizes) == (1, True, (MEMBERS - CRASHES,))
    assert rounds <= SPREAD + 1  # the re-stamped alerts redeliver within the spread
    assert paths(vc) == (0, 0, 1)  # the steps' rounds came with the wave's fetch
    assert (vc.activity["decisions_classic"], vc.activity["decisions_fast"]) == (0, 1)


def test_steps_and_the_fused_decision_carry_the_counts_too(compiled):
    """``test_contested_round_fallback_picks_plurality``'s cluster through the
    index seam: a step fetches nothing, so its rounds' counts arrive with the
    next verb that fetches."""
    n = 120
    vc = VirtualCluster.create(n, fd_threshold=2, seed=11)
    cohort_of = np.zeros(n, dtype=np.int32)
    cohort_of[80:] = 1
    vc.assign_cohorts(cohort_of)
    vc.crash([10, 60])
    vc.set_partition([1], np.asarray(vc.state.obs_idx)[:, 60])
    fetched = vc.metrics.counters["engine_d2h_bytes"]
    for _ in range(4):
        vc.step()
    assert paths(vc) == (0, 0, 0) and vc.metrics.counters["engine_d2h_bytes"] == fetched
    rounds, decided, _, members = vc.run_to_decision(max_steps=64)
    assert decided and members == n - 2 and rounds + 4 == 2 + vc.cfg.fallback_rounds - 1
    assert paths(vc) == (1, 1, 0)
    assert vc.metrics.counters["engine_d2h_bytes"] == fetched + 4 + 12  # the packed scalar and the counts
    # a quiet cluster afterwards counts nothing, and a second cut counts on
    vc.crash([20])
    vc.set_partition([], [])
    assert vc.run_to_decision(max_steps=64)[1]
    assert paths(vc) == (1, 1, 1)


# -- (c) a cluster that never sets a partition ---------------------------------


def _shapes(cfg):
    identity = [jax.ShapeDtypeStruct((cfg.k, cfg.n), jnp.uint32)] * 2 + [
        jax.ShapeDtypeStruct((cfg.n,), jnp.uint32)] * 2 + [jax.ShapeDtypeStruct((cfg.n,), bool)]
    return (jax.eval_shape(lambda *a: initial_state(cfg, *a), *identity),
            jax.eval_shape(lambda: FaultInputs.none(cfg)))


_I32 = jax.ShapeDtypeStruct((), jnp.int32)


@pytest.mark.parametrize("impl,controls", [
    (vcm.engine_step_impl, ()),
    (vcm.engine_step_carried_impl, ("masks",)),
    (vcm.run_to_decision_impl, (_I32,)),
    (lambda cfg, s, f, target, steps, least, **lanes: vcm.run_until_membership_impl(
        cfg, s, f, target, steps, 4, least, **lanes), (_I32, _I32, _I32)),
], ids=["step", "carried step", "decision", "wave"])
def test_unset_counts_trace_the_program_of_no_counts(compiled, impl, controls):
    """Every body a one-device round program jits: without the keyword and
    with ``paths=None`` the jaxpr is one text, so a cluster that never sets a
    partition traces what it traced."""
    cfg = small().cfg
    state, faults = _shapes(cfg)
    if controls == ("masks",):
        controls = (jax.eval_shape(lambda s, f: vcm._edge_masks(cfg, s, f), state, faults),)
    without = jax.make_jaxpr(lambda s, f, *c: impl(cfg, s, f, *c))(state, faults, *controls)
    unset = jax.make_jaxpr(lambda s, f, *c: impl(cfg, s, f, *c, paths=None))(state, faults, *controls)
    assert str(without) == str(unset)
    counts = jax.ShapeDtypeStruct((3,), jnp.int32)
    counted = jax.make_jaxpr(lambda s, f, p, *c: impl(cfg, s, f, *c, paths=p))(
        state, faults, counts, *controls)
    assert len(counted.out_avals) == len(without.out_avals) + 1
    assert counted.out_avals[-1].shape == (3,) and str(counted) != str(without)


def test_a_cluster_that_never_sets_a_partition_fetches_what_it_did(compiled):
    vc = twin(telemetry=False, members=500, slots=512)
    vc.crash([3, 77])
    vc.sync()
    fetched = vc.metrics.counters["engine_d2h_bytes"]
    rounds, cuts, resolved, _ = vc.run_until_membership(498, max_steps=64, max_cuts=4, min_cuts=1)
    assert (cuts, resolved) == (1, True)
    assert vc.metrics.counters["engine_d2h_bytes"] - fetched == 12 + 4 * 4  # three scalars and max_cuts sizes
    assert vc.paths is None
    assert not set(CONSENSUS_PATH_COUNTERS) & set(vc.metrics.counters)
    assert "inject_partition" not in vc.metrics.phase_timings["engine_dispatch"]
    assert "engine_classic_rounds" not in vc.prometheus_text()
    vc.set_partition([], [])  # the first call mints the three series, whatever it names
    assert paths(vc) == (0, 0, 0) and vc.paths is not None
    for name in CONSENSUS_PATH_COUNTERS:
        assert f"rapid_{name}_total" in vc.prometheus_text()
    assert set(CONSENSUS_PATH_COUNTERS) <= set(vc.telemetry_snapshot()["metrics"])
    vc.crash([9])
    vc.sync()
    fetched = vc.metrics.counters["engine_d2h_bytes"]
    vc.run_until_membership(497, max_steps=64, max_cuts=4, min_cuts=1)
    assert vc.metrics.counters["engine_d2h_bytes"] - fetched == 28 + 12  # the counts ride the same fetch
    assert paths(vc) == (0, 0, 1)
