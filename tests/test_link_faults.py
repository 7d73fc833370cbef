"""One-way link faults as a device-resident lane (models/state.LinkFaults).

A faulty member loses a share of what is sent TO it and keeps sending, on an
on/off schedule in rounds; ``VirtualCluster.set_link_faults`` sets the lane by
index scatter and every round draws its probes' outcomes on the device. The
system is held against ``benchmarks/link_model.py`` (the paper's windowed
detector replayed edge by edge in numpy, no code shared with the engine) and,
for the view, against ``benchmarks/membership_model.py`` with the faulty set as
its crashed set:

(a) the engine's own draws, fetched round by round, replayed by the reference:
    ``fd_fired`` / ``fire_round`` equal edge for edge;
(b) the draws' statistics; (c) Fig. 9 at N = 400; (d) blips age out of the
window and latch in the counter; (e) false reports stay under L; (f) a deaf
member casts no vote; (g) the differential against the host protocol path;
(h) an unset lane is no lane, and a set one rides sync, copies and checkpoints;
(i) the benchmark's configuration and a small twin of each of its cells.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import link_model, membership_model
from rapid_tpu.models import virtual_cluster as vcm
from rapid_tpu.models.state import LinkFaults
from rapid_tpu.models.virtual_cluster import VirtualCluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, THRESHOLD = 10, 4
#: policy -> (fd_window, quiet rounds run before the lane is set)
POLICIES = {"windowed": (WINDOW, WINDOW), "counter": (0, 0)}

draws = jax.jit(vcm.link_probe_draws, static_argnums=(0,))  # donate-ok: reads two lanes of a state that stays live


@pytest.fixture(scope="module")
def compiled():
    """Every test of the module takes this: tier-1 runs near the process's
    limit of memory maps, so the module gives back what it compiled."""
    yield True
    jax.clear_caches()


def cluster(n, policy="windowed", *, cohorts=2, seed=12, spread=0, warm=True):
    window, quiet = POLICIES[policy]
    vc = VirtualCluster.create(
        n, k=10, h=9, l=4, cohorts=cohorts, fd_threshold=THRESHOLD, fd_window=window,
        seed=seed, delivery_spread=spread)
    vc.assign_cohorts_roundrobin()
    for _ in range(quiet if warm else 0):
        vc.step()
    return vc


def probed_edges(vc) -> np.ndarray:
    """[n, k]: the edges whose observer probes this round (the reference's
    own reading of the topology: an alive subject with an alive observer)."""
    obs = np.asarray(vc.state.obs_idx).T
    alive = np.asarray(vc.state.alive)
    return (obs >= 0) & alive[np.clip(obs, 0, None)] & alive[:, None]


def clean_faulty_set(vc, size, rng) -> list:
    """A faulty set in which no healthy member has L of its observers."""
    observers = np.asarray(vc.state.obs_idx)
    while True:
        faulty = np.sort(rng.choice(int(vc.state.n_members), size=size, replace=False))
        reports = link_model.false_reports(observers, faulty)
        reports[faulty] = 0
        if (reports < vc.cfg.l).all():
            return faulty.tolist()


# -- (a) the detector, edge for edge -----------------------------------------


@pytest.mark.parametrize("policy", ["windowed", "counter"])
@pytest.mark.parametrize("permille", [1000, 800, 300])
def test_fired_edges_equal_the_reference_replay(compiled, permille, policy):
    vc = cluster(64, policy, spread=2)  # delivery takes rounds: edges fire before the cut
    faulty = [5, 17, 40]
    first_round = int(vc.state.round_idx)
    observers = np.asarray(vc.state.obs_idx)
    vc.set_link_faults(faulty, permille, seed=permille)
    model = link_model.EdgeDetectors(
        64, 10, POLICIES[policy][0], THRESHOLD, rounds_seen=POLICIES[policy][1])
    compared = fired_some = 0
    for r in range(16):
        lost, _ = draws(vc.cfg, vc.state, vc.links)
        probed = probed_edges(vc)
        events = vc.step()
        if bool(events.decided):  # the view change wipes the detectors: stop before it
            break
        model.step(first_round + r, probed, np.asarray(lost))
        np.testing.assert_array_equal(np.asarray(vc.state.fd_fired), model.fired)
        fired = model.fired
        np.testing.assert_array_equal(
            np.asarray(vc.state.fire_round)[fired], model.fire_round[fired])
        compared += 1
        fired_some += int(fired.any())
    assert compared >= 4 and fired_some, "the comparison has to see edges fire"
    # true and false reports both: edges into the set and edges out of it
    if permille == 1000:
        assert model.fired[faulty].all()
        out_of_set = np.isin(observers.T, faulty) & ~np.isin(np.arange(64), faulty)[:, None]
        assert model.fired[out_of_set].all() and out_of_set.any()


# -- (b) the draws -----------------------------------------------------------


@pytest.mark.parametrize("permille", [800, 300])
def test_draws_fail_at_the_two_ends_loss(compiled, permille):
    vc = cluster(400, warm=False)
    faulty = np.arange(0, 400, 4)  # a quarter: every class of edge is well filled
    vc.set_link_faults(faulty, permille, seed=3)
    observers = np.asarray(vc.state.obs_idx).T  # [n, k]
    loss = link_model.loss_in_round(faulty, permille, 400, 0)
    expect = link_model.failure_probability(loss[:, None], loss[observers])
    rounds = [
        np.asarray(draws(vc.cfg, vc.state._replace(round_idx=jnp.int32(r)), vc.links)[0])
        for r in range(14)
    ]
    assert (rounds[0] != rounds[1]).any(), "a frozen mask is no draw"
    failed = np.sum(rounds, axis=0)  # [n, k] failures over the rounds
    total = 0
    for p in np.unique(expect):
        edges = expect == p
        trials = int(edges.sum()) * len(rounds)
        if p == 0.0:
            assert failed[edges].sum() == 0  # two healthy ends: no draw decides it
            continue
        total += trials
        sigma = np.sqrt(trials * p * (1 - p))
        assert abs(failed[edges].sum() - trials * p) <= 3 * sigma, (p, trials)
    assert total >= 20_000


def test_dead_ingress_loses_every_probe_and_the_off_phase_none(compiled):
    vc = cluster(64, warm=False)
    faulty = [3, 9]
    vc.set_link_faults(faulty, 1000, on_rounds=2, off_rounds=3)
    touches = np.isin(np.asarray(vc.state.obs_idx).T, faulty)
    touches[faulty] = True
    for age in range(10):
        lane = vc.links._replace(age=jnp.int32(age))
        lost, deaf = draws(vc.cfg, vc.state, lane)
        on = link_model.schedule_on(age, 2, 3)
        np.testing.assert_array_equal(np.asarray(lost), touches & on)
        np.testing.assert_array_equal(
            ~np.asarray(deaf), link_model.may_vote(faulty, 1000, 64, age, 2, 3))


# -- (c) Fig. 9 --------------------------------------------------------------


def test_flip_flop_removes_exactly_the_faulty_set_in_the_first_on_phase(compiled):
    vc = cluster(400, cohorts=4, spread=2)
    faulty = clean_faulty_set(vc, 4, np.random.default_rng(9))
    model = membership_model.MembershipModel(np.ones((1, 400), dtype=bool))
    model.apply(np.array([(0, s) for s in faulty]), np.zeros((0, 2), dtype=int))
    touching = np.isin(np.asarray(vc.state.obs_idx).T, faulty)
    touching[faulty] = True
    vc.set_link_faults(faulty, 1000, on_rounds=20, off_rounds=20)
    rounds, cuts, resolved, sizes = vc.run_until_membership(396, max_steps=64, max_cuts=4, min_cuts=1)
    assert resolved and cuts == 1 and sizes == (396,) and rounds < 20
    numbers = model.compare_view(vc.alive_mask[None])
    assert membership_model.failures(numbers) == 0, numbers
    # 4 members x (10 edges in + 10 out) a round, an edge between two of them
    # once: counted on the device, fetched with the decision
    assert vc.metrics.counters["engine_link_probes_lost"] == int(touching.sum()) * rounds


def test_six_flips_by_hand_end_where_the_probe_mask_test_ends(compiled):
    """``tests/test_engine.py::test_flip_flop_partition_removes_exactly_faulty_set``
    flips a probe-failure mask on and off by hand, three rounds each; here the
    lane's schedule does the flipping, and the faulty members also report."""
    n, faulty = 400, list(range(40, 50))
    vc = VirtualCluster.create(n, k=10, h=9, l=4, fd_threshold=4, seed=12)
    reports = link_model.false_reports(np.asarray(vc.state.obs_idx), faulty)
    assert reports[np.setdiff1d(np.arange(n), faulty)].max() < vc.cfg.l
    vc.set_link_faults(faulty, 1000, on_rounds=3, off_rounds=3)
    healthy = np.setdiff1d(np.arange(n), faulty)
    for _ in range(6):
        for _ in range(3):
            vc.step()
        assert vc.alive_mask[healthy].all()
    vc.set_link_faults(faulty, 1000)
    vc.run_until_converged(max_steps=32)
    alive = vc.alive_mask
    assert not alive[faulty].any() and alive[healthy].all()
    assert vc.membership_size == n - len(faulty)


# -- (d) what the paper's policy is for --------------------------------------


@pytest.mark.parametrize("policy,cuts_expected", [("windowed", 0), ("counter", 1)])
def test_blips_age_out_of_the_window_and_latch_in_the_counter(compiled, policy, cuts_expected):
    vc = cluster(64, policy)
    vc.set_link_faults([7, 21], 1000, on_rounds=3, off_rounds=20)
    _, cuts, _, _ = vc.run_until_membership(62, max_steps=70, max_cuts=2, min_cuts=1)
    assert cuts == cuts_expected
    assert bool(np.asarray(vc.state.fd_fired).any()) is False  # none, or wiped by the cut
    assert vc.membership_size == 64 - 2 * cuts_expected


# -- (e) false reports stay under L ------------------------------------------


def test_false_reports_are_carried_and_never_proposed(compiled):
    """The round itself, without the view change that wipes the tallies: in
    the round that decides, every healthy subject of a faulty observer carries
    that observer's ring bit in every cohort, and the cut is the faulty set."""
    vc = cluster(400, cohorts=4)
    faulty = clean_faulty_set(vc, 4, np.random.default_rng(4))
    vc.set_link_faults(faulty, 1000)
    observers = np.asarray(vc.state.obs_idx)  # [k, n]
    named = np.isin(np.arange(400), faulty)
    one_round = jax.jit(vcm._compute_round, static_argnums=(0,))  # donate-ok: a test's own round
    state, links = vc.state, vc.links
    for _ in range(12):
        state, decided, winner, _, links = one_round(vc.cfg, state, vc.faults, links=links)
        assert not (np.asarray(state.prop_mask).any(axis=0) & ~named).any(), "a healthy member was proposed"
        if bool(decided):
            break
    assert bool(decided)
    np.testing.assert_array_equal(np.asarray(winner), named)
    bits = np.asarray(state.report_bits)  # [c, n]
    false_edges = list(zip(*np.nonzero(named[observers] & ~named[None, :])))
    assert len(false_edges) >= 30
    for ring, subject in false_edges:
        assert ((bits[:, subject] >> ring) & 1).all(), (ring, subject)
    tallies = np.array([bin(int(word)).count("1") for word in bits[0]])
    assert tallies[~named].max() < vc.cfg.l <= vc.cfg.h <= tallies[named].min()


# -- (f) who votes -----------------------------------------------------------


@pytest.mark.parametrize("permille,votes", [(1000, False), (800, True)])
def test_a_deaf_member_casts_no_vote_and_the_fast_path_commits(compiled, permille, votes):
    vc = cluster(400, cohorts=4)
    faulty = clean_faulty_set(vc, 4, np.random.default_rng(5))
    vc.set_link_faults(faulty, permille, seed=11)
    for _ in range(40):
        before = np.asarray(vc.state.vote_valid)
        events = vc.step()
        if bool(events.decided):
            break
        cast = np.asarray(vc.state.vote_valid)
        if cast.any():
            assert cast[faulty].all() == votes and cast[faulty].any() == votes
            assert cast[np.setdiff1d(np.arange(400), faulty)].all()
        assert not (before & ~cast).any()
    assert bool(events.decided) and bool(events.fast_decided)
    assert int(events.total_votes) == (400 if votes else 396)
    assert vc.membership_size == 396


# -- (g) the differential against the host protocol path ---------------------


@pytest.mark.parametrize("seed", range(1, 9))
def test_oneway_partitions_agree_with_the_host_path(compiled, seed, monkeypatch):
    from rapid_tpu.sim import oracles
    from rapid_tpu.sim.fuzz import asymmetric_link, run_schedule

    lanes = []
    set_lane = VirtualCluster.set_link_faults
    monkeypatch.setattr(
        VirtualCluster, "set_link_faults",
        lambda self, slots, *a, **kw: (lanes.append(list(slots)), set_lane(self, slots, *a, **kw))[1])
    result = run_schedule(asymmetric_link(seed))
    assert result.final_converged
    assert oracles.check_differential(result) == []
    # the replay went through the lane, or the lane could not stand for the
    # host's run (a healthy member with L of its observers deaf) and said so
    victim = next(e.slots[0] for e in result.schedule.events if e.kind == "partition_oneway")
    assert lanes in ([[victim]], [])


def test_most_oneway_replays_take_the_lane(compiled):
    from rapid_tpu.sim import oracles
    from rapid_tpu.sim.faults import FaultEvent
    from rapid_tpu.sim.scenario import endpoints_for

    took = 0
    for seed in range(8):
        endpoints = endpoints_for(seed, 12)
        vc = VirtualCluster.from_endpoints(
            list(endpoints), n_slots=12, n_members=8, k=10, h=9, l=4, fd_threshold=1)
        assert oracles.inject_engine_event(vc, FaultEvent("partition_oneway", (2,))) == -1
        took += vc.links is not None
        assert (vc.links is None) == bool(np.asarray(vc.faults.crashed)[2])
        _, decided, winner, members = vc.run_to_decision(max_steps=48)
        assert decided and members == 7 and np.nonzero(np.asarray(winner))[0].tolist() == [2]
    assert took >= 4
    vc = VirtualCluster.from_endpoints(list(endpoints), n_slots=12, n_members=8, fd_threshold=1)
    oracles.inject_engine_event(vc, FaultEvent("partition_oneway", (2,)), oneway_as_crash=True)
    assert vc.links is None and bool(np.asarray(vc.faults.crashed)[2])


# -- (h) no lane is the program of before; a set lane is carried -------------


def _shapes(cfg):
    from rapid_tpu.models.state import FaultInputs, initial_state

    n, k = cfg.n, cfg.k
    u32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32)  # noqa: E731
    state = jax.eval_shape(
        lambda *identity: initial_state(cfg, *identity),
        u32(k, n), u32(k, n), u32(n), u32(n), jax.ShapeDtypeStruct((n,), jnp.bool_))
    return state, jax.eval_shape(lambda: FaultInputs.none(cfg))


@pytest.mark.parametrize("impl,controls", [
    (vcm.engine_step_impl, ()),
    (vcm.run_to_decision_impl, (jax.ShapeDtypeStruct((), jnp.int32),)),
])
def test_an_unset_lane_traces_the_program_of_no_lane(compiled, impl, controls):
    cfg = cluster(64, warm=False).cfg
    state, faults = _shapes(cfg)
    without = jax.make_jaxpr(lambda s, f, *c: impl(cfg, s, f, *c))(state, faults, *controls)
    unset = jax.make_jaxpr(lambda s, f, *c: impl(cfg, s, f, *c, links=None))(state, faults, *controls)
    assert str(without) == str(unset)
    lane = jax.eval_shape(lambda: LinkFaults.none(cfg))
    with_lane = jax.make_jaxpr(lambda s, f, l, *c: impl(cfg, s, f, *c, links=l))(
        state, faults, lane, *controls)
    assert len(with_lane.out_avals) == len(without.out_avals) + len(lane)
    assert str(with_lane) != str(without)


def _gathers(jaxpr, inside_loop=False) -> tuple:
    """(gathers outside every loop, gathers inside one) of a jaxpr."""
    outside = inside = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            inside, outside = inside + inside_loop, outside + (not inside_loop)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    deeper = _gathers(sub, inside_loop or eqn.primitive.name == "while")
                    outside, inside = outside + deeper[0], inside + deeper[1]
    return outside, inside


def test_the_lanes_gather_is_made_once_a_convergence(compiled):
    """The loss at every edge's observer reads the lane and the topology alone:
    the fused loops gather it before their rounds, as they hoist the masks."""
    cfg = cluster(64, warm=False).cfg
    state, faults = _shapes(cfg)
    lane = jax.eval_shape(lambda: LinkFaults.none(cfg))
    steps = jax.ShapeDtypeStruct((), jnp.int32)
    bare = jax.make_jaxpr(lambda s, f, m: vcm.run_to_decision_impl(cfg, s, f, m))(state, faults, steps)
    laned = jax.make_jaxpr(lambda s, f, m, l: vcm.run_to_decision_impl(cfg, s, f, m, links=l))(
        state, faults, steps, lane)
    (out_bare, in_bare), (out_laned, in_laned) = _gathers(bare.jaxpr), _gathers(laned.jaxpr)
    assert in_laned == in_bare and out_laned == out_bare + 1


def test_a_cluster_without_a_lane_dispatches_no_lane_program(compiled):
    vc = cluster(64, warm=False)
    vc.crash([3])
    assert vc.links is None
    vc.run_to_decision(max_steps=32)
    assert vc.links is None and "engine_link_probes_lost" not in vc.metrics.counters
    assert "inject_link_faults" not in vc.metrics.phase_timings.get("engine_dispatch", {})
    vc.set_link_faults([5], 800)
    assert vc.metrics.counters["engine_link_probes_lost"] == 0
    assert "inject_link_faults" in vc.metrics.phase_timings["engine_dispatch"]
    assert 'rapid_engine_link_probes_lost_total' in vc.prometheus_text()
    vc.set_link_faults([])  # clear: the same call with no slots
    assert vc.links is None


def test_the_setter_checks_its_arguments_on_the_host(compiled):
    vc = cluster(64, warm=False)
    with pytest.raises(IndexError):
        vc.set_link_faults([64])
    with pytest.raises(ValueError, match="loss_permille"):
        vc.set_link_faults([1], 1001)
    with pytest.raises(ValueError, match="on_rounds"):
        vc.set_link_faults([1], 1000, on_rounds=0, off_rounds=5)
    assert vc.links is None
    uploaded = vc.metrics.counters["engine_h2d_bytes"]
    vc.set_link_faults([1, 2, 3], 1000)
    assert vc.metrics.counters["engine_h2d_bytes"] - uploaded == 3 * 4 + 4 * 4  # indices and four scalars


def test_a_mesh_refuses_the_lane(compiled):
    from rapid_tpu.parallel.mesh import make_mesh

    vc = VirtualCluster.create(64, cohorts=2, mesh=make_mesh(jax.devices()[:2], shape=(1, 2)))
    with pytest.raises(ValueError, match="mesh"):
        vc.set_link_faults([1])


def test_sync_and_a_device_copy_carry_a_set_lane(compiled):
    vc = cluster(64)
    unset = vc.sync()
    vc.set_link_faults([5, 17], 800, seed=2)
    assert vc.sync() != unset  # the checksum reads the lane: the scatter is behind the barrier
    clone = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))  # donate-ok: a test's copy
    pristine = (clone(vc.state), vc.faults, vc.links)
    first = vc.run_until_membership(62, max_steps=64, max_cuts=4, min_cuts=1)
    lost = vc.metrics.counters["engine_link_probes_lost"]
    vc.state, vc.faults, vc.links = clone(pristine[0]), pristine[1], pristine[2]
    assert vc.run_until_membership(62, max_steps=64, max_cuts=4, min_cuts=1) == first
    assert vc.metrics.counters["engine_link_probes_lost"] == 2 * lost > 0
    assert int(vc.links.age) == first[0]


def test_a_checkpoint_round_trip_carries_a_set_lane(compiled, tmp_path):
    from rapid_tpu.utils.checkpoint import load_link_faults, load_serving_state, save_serving_state

    vc = cluster(64)
    vc.set_link_faults([5, 17], 800, on_rounds=4, off_rounds=2, seed=2)
    vc.step()
    save_serving_state(tmp_path / "lane.npz", vc.cfg, vc.state, vc.faults, links=vc.links)
    save_serving_state(tmp_path / "bare.npz", vc.cfg, vc.state, vc.faults)
    assert load_link_faults(tmp_path / "bare.npz") is None  # an archive without it loads as unset
    lane = load_link_faults(tmp_path / "lane.npz")
    for got, want in zip(lane, vc.links):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    cfg, state, faults, _, _ = load_serving_state(tmp_path / "lane.npz")
    resumed = VirtualCluster(cfg, state)
    resumed.faults, resumed.links = faults, lane
    assert resumed.run_until_membership(62, max_steps=64, max_cuts=4, min_cuts=1) == \
        vc.run_until_membership(62, max_steps=64, max_cuts=4, min_cuts=1)
    np.testing.assert_array_equal(resumed.alive_mask, vc.alive_mask)


# -- (i) the benchmark's configuration and its cells, small ------------------


def held(*parts):
    with open(os.path.join(REPO, *parts), encoding="utf-8") as handle:
        return json.load(handle)


def test_the_configuration_is_the_sources(compiled):
    config = held("benchmarks", "configs", "cluster-50k.json")
    assert (config["members"], config["slots"]) == (50_000, 50_000)
    assert (config["k"], config["h"], config["l"]) == (10, 9, 4)
    assert (config["fd_window"], config["fd_threshold"], config["fd_stagger_rounds"]) == (10, 4, 0)
    assert config["cohorts"] == 64 and config["use_pallas"] and config["reduced"] == []
    assert config["deployment"] == "cluster_link" and len(config["source"]) <= 200
    assert "configs[3]" in config["source"] and "Figs. 9-10" in config["source"]
    for name, permille, schedule in (("loss80", 800, (0, 0)), ("flipflop", 1000, (20, 20))):
        traffic = held("benchmarks", "traffic", name + ".json")
        assert traffic["kind"] == "link_faults" and traffic["faulty_share"] == 0.01
        assert traffic["ingress_loss_permille"] == permille
        assert (traffic["on_rounds"], traffic["off_rounds"]) == schedule
        assert (traffic["plan_cycle"], traffic["arrival_seed"]) == (16, 7)


class _Context:
    """What ``benchmarks/harness.py`` hands a generator, without its clocks."""

    def __init__(self, config, traffic, seed):
        import contextlib

        self.config, self.traffic, self.seed = config, traffic, seed
        self.platform, self.seconds, self.run = "cpu", 0.0, {}
        self.span = lambda name: contextlib.nullcontext()
        self.window = lambda target: _OneCycle()

    def build_target(self, seed):
        from benchmarks import targets

        return targets.build(self.config, seed, self.platform)


class _OneCycle:
    """A window that stays open for one cycle: its clock is asked before each."""

    asked = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def elapsed(self):
        self.asked += 1
        return -1.0 if self.asked == 1 else 1.0


@pytest.mark.parametrize("seed", [7001, 2**31 + 11])
@pytest.mark.parametrize("traffic_name", ["loss80", "flipflop"])
def test_a_small_twin_of_each_cell_is_correct(compiled, traffic_name, seed):
    from benchmarks.generators import link_faults

    config = dict(held("benchmarks", "configs", "cluster-50k.json"), members=2000, slots=2000, cohorts=4)
    traffic = dict(held("benchmarks", "traffic", traffic_name + ".json"), plan_cycle=4)
    record = link_faults.run(_Context(config, traffic, seed))
    assert record["attempted"] == 4 and record["failed"] == 0
    assert membership_model.failures(record["checks"]) == 0, record["checks"]
    assert record["view_changes"] == 4 and sorted(record["commit_plan"]) == [0, 1, 2, 3]
    assert all(rounds < 20 for rounds in record["commit_rounds"])
