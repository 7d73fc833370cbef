"""One-way link faults in every cluster of a fleet: the ``LinkFaults`` lane
stacked over the tenants (``TenantFleet.set_link_faults``).

The fleet's twin of ``tests/test_link_faults.py``. The per-tenant function is
the cluster's, so the bar is the fleet's own: a fleet whose tenants have
different faulty sets, losses, schedules and seeds is bit-identical, state
and lane, to the same tenants as separate ``VirtualCluster``s through
``step``, ``run_to_decision`` and ``run_until_membership``, tenants that end
in different rounds and a quarantined one included. Beside it:

(a) the differential; (b) fired edges against ``benchmarks/link_model.py``,
tenant by tenant; (c) false reports under L; (d) an unset lane is no lane
(all four fleet programs, and the driver); (e) where the whole-wave loop
makes the lane's gather; (f) the setter; (g) the mesh factories; (h) sync,
copies, ``from_clusters`` and checkpoints carry it; (i) the benchmark's
configuration, a small twin of its cell and both controls.
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
from rapid_tpu.tenancy import fleet as fleetm
from rapid_tpu.tenancy.fleet import TenantFleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, WINDOW, THRESHOLD = 64, 10, 4
GEOMETRY = dict(k=10, cohorts=2, fd_window=WINDOW, delivery_spread=2)
KNOBS = (9, 3, THRESHOLD)  # the paper's watermarks and its detector
#: tenant -> (faulty slots, loss in permille, on rounds, off rounds, draw seed):
#: two steady lossy ingresses, a third (quarantined in the wave), a dead one that
#: flip-flops, a tenant nobody is faulty in
TENANTS = (
    ((5, 17), 800, 0, 0, 11),
    ((3, 30), 450, 0, 0, 12),
    ((9, 40, 41), 600, 0, 0, 2**32 - 5),
    ((7, 21), 1000, 6, 3, 14),
    ((), 0, 0, 0, 15),
)
IDENTITIES = tuple(range(20, 20 + len(TENANTS)))
WAVE = dict(max_steps=64, max_cuts=4)
VERBS = ("step", "run_to_decision", "run_until_membership")


@pytest.fixture(scope="module")
def compiled():
    """Every test of the module takes this: tier-1 runs near the process's
    limit of memory maps, so the module gives back what it compiled."""
    yield True
    jax.clear_caches()


def pairs_of(tenants=TENANTS) -> list:
    return [(t, slot) for t, (slots, *_) in enumerate(tenants) for slot in slots]


def controls_of(tenants=TENANTS) -> dict:
    loss, on, off, seeds = zip(*(tenant[1:] for tenant in tenants))
    return dict(loss_permille=list(loss), on_rounds=list(on), off_rounds=list(off), seeds=list(seeds))


def make_fleet(tenants=TENANTS, *, warm=True, **kw) -> TenantFleet:
    fleet = TenantFleet.create(
        len(tenants), N, seeds=list(IDENTITIES[: len(tenants)]), knobs=[KNOBS] * len(tenants),
        **{**GEOMETRY, **kw})
    for _ in range(WINDOW if warm else 0):
        fleet.step()
    return fleet


def make_cluster(t: int, *, warm=True) -> VirtualCluster:
    h, l, fd = KNOBS
    vc = VirtualCluster.create(N, h=h, l=l, fd_threshold=fd, seed=IDENTITIES[t], **GEOMETRY)
    vc.assign_cohorts_roundrobin()
    for _ in range(WINDOW if warm else 0):
        vc.step()
    return vc


def set_on_cluster(vc: VirtualCluster, t: int) -> None:
    slots, loss, on, off, seed = TENANTS[t]
    vc.set_link_faults(list(slots), loss, on_rounds=on, off_rounds=off, seed=seed)


def targets_of(tenants=TENANTS) -> list:
    return [N - len(slots) for slots, *_ in tenants]


def leaves_of(tree, t=None) -> dict:
    """A state's or a lane's leaves as host arrays (tenant ``t``'s slice)."""
    return {
        name: np.asarray(leaf) if t is None else np.asarray(leaf)[t]
        for name, leaf in tree._asdict().items()
    }


def assert_same(ours: dict, theirs: dict, what: str) -> None:
    assert ours.keys() == theirs.keys()
    for name in ours:
        assert ours[name].dtype == theirs[name].dtype, (what, name)
        np.testing.assert_array_equal(ours[name], theirs[name], err_msg=f"{what}.{name}")


# -- (a) a fleet is its tenants, state and lane -------------------------------

QUARANTINED = 2  # in the wave: frozen from its first round, state and lane


def drive(driver, verb: str, target=None, min_cuts=None):
    if verb == "step":
        for _ in range(14):
            driver.step()
        return None
    if verb == "run_to_decision":
        rounds, decided, winner, members = driver.run_to_decision(max_steps=48)
        return rounds, decided, np.asarray(winner), members
    return driver.run_until_membership(target, min_cuts=min_cuts, **WAVE)


@pytest.fixture(scope="module")
def differential():
    """Every verb once: the fleet, and the same tenants as clusters of their
    own (the tenant without a faulty member sets no lane: it runs the
    programs of a cluster that never had one)."""
    seen = {}
    for verb in VERBS:
        fleet = make_fleet()
        fleet.set_link_faults(pairs_of(), **controls_of())
        wave = verb == "run_until_membership"
        if wave:
            fleet.quarantine([QUARANTINED])
        before = (leaves_of(fleet.state, QUARANTINED), leaves_of(fleet.links, QUARANTINED))
        min_cuts = [int(bool(slots)) for slots, *_ in TENANTS]
        observed = drive(fleet, verb, targets_of(), min_cuts)
        clusters = []
        for t in range(len(TENANTS)):
            vc = make_cluster(t)
            if TENANTS[t][0]:
                set_on_cluster(vc, t)
            driven = None
            if not (wave and t == QUARANTINED):
                driven = drive(vc, verb, targets_of()[t], min_cuts[t])
            clusters.append((vc, driven))
        seen[verb] = dict(fleet=fleet, observed=observed, clusters=clusters, before=before)
    yield seen
    jax.clear_caches()


@pytest.mark.parametrize("verb", VERBS)
def test_every_tenants_state_is_its_own_clusters(differential, verb):
    run = differential[verb]
    for t, (vc, _) in enumerate(run["clusters"]):
        assert_same(leaves_of(run["fleet"].state, t), leaves_of(vc.state), f"{verb}: state[{t}]")


@pytest.mark.parametrize("verb", VERBS)
def test_every_tenants_lane_is_its_own_clusters(differential, verb):
    run = differential[verb]
    none = LinkFaults.none(run["fleet"].cfg)
    for t, (vc, _) in enumerate(run["clusters"]):
        ours = leaves_of(run["fleet"].links, t)
        if vc.links is not None:
            assert_same(ours, leaves_of(vc.links), f"{verb}: links[{t}]")
            continue
        # a tenant without a pair: loss 0 everywhere, so nothing is ever
        # lost; its clock runs with the rounds it is driven
        assert not TENANTS[t][0] and ours["probes_lost"] == 0 and not ours["loss_permille"].any()
        assert {n: ours[n].dtype for n in ours} == {n: np.asarray(v).dtype for n, v in none._asdict().items()}


@pytest.mark.parametrize("verb", VERBS[1:])
def test_the_verbs_fetch_is_the_clusters(differential, verb):
    run = differential[verb]
    for t, (_, driven) in enumerate(run["clusters"]):
        ours = [np.asarray(part)[t] for part in run["observed"]]
        if driven is None:  # the quarantined tenant: no round, no cut
            assert (ours[0], ours[1]) == (0, 0)
            continue
        if verb == "run_until_membership":
            rounds, cuts, resolved, sizes = driven
            assert (ours[0], ours[1], bool(ours[2])) == (rounds, cuts, resolved), t
            assert tuple(size for size in ours[3].tolist() if size >= 0) == sizes
        else:
            rounds, decided, winner, members = driven
            assert (ours[0], bool(ours[1]), ours[3]) == (rounds, decided, members), t
            np.testing.assert_array_equal(ours[2], winner)


def test_the_tenants_end_in_different_rounds_and_each_clock_stops_with_its_own(differential):
    run = differential["run_until_membership"]
    rounds, cuts, resolved, _ = run["observed"]
    serving = [t for t in range(len(TENANTS)) if t != QUARANTINED]
    faulty = [t for t in serving if TENANTS[t][0]]
    assert resolved[serving].all() and len(set(rounds[faulty].tolist())) == len(faulty) >= 3
    assert (cuts[faulty] >= 1).all()
    # a tenant outside ``active`` keeps its clock and its count: every lane
    # is as old as the rounds its own tenant ran, not as the slowest one's
    np.testing.assert_array_equal(np.asarray(run["fleet"].links.age), rounds)
    assert rounds.max() > rounds[faulty].min() > 0
    lost = run["fleet"].link_probes_lost()
    np.testing.assert_array_equal(lost, np.asarray(run["fleet"].links.probes_lost))
    assert run["fleet"].metrics.counters["engine_link_probes_lost"] == lost.sum() > 0
    decision = differential["run_to_decision"]
    np.testing.assert_array_equal(np.asarray(decision["fleet"].links.age), decision["observed"][0])


def test_a_quarantined_tenant_keeps_state_and_lane(differential):
    run = differential["run_until_membership"]
    state, lane = run["before"]
    assert_same(leaves_of(run["fleet"].state, QUARANTINED), state, "state")
    assert_same(leaves_of(run["fleet"].links, QUARANTINED), lane, "links")
    assert lane["age"] == 0 and lane["probes_lost"] == 0 and lane["loss_permille"].sum() == 3 * 600


# -- (b) the detector, edge for edge, tenant by tenant -------------------------


def test_fired_edges_equal_the_reference_replay_in_every_tenant(compiled):
    fleet = make_fleet()
    cfg = fleet.cfg
    draws = jax.jit(jax.vmap(lambda s, lane: vcm.link_probe_draws(cfg, s, lane)))  # donate-ok: reads two lanes of a state that stays live
    first_round = np.asarray(fleet.state.round_idx)
    fleet.set_link_faults(pairs_of(), **controls_of())
    models = [
        link_model.EdgeDetectors(N, 10, WINDOW, THRESHOLD, rounds_seen=WINDOW) for _ in TENANTS]
    live = [True] * len(TENANTS)
    compared, fired_in = [0] * len(TENANTS), set()
    for r in range(16):
        lost = np.asarray(draws(fleet.state, fleet.links)[0])
        obs = np.asarray(fleet.state.obs_idx).transpose(0, 2, 1)  # [t, n, k]
        alive = np.asarray(fleet.state.alive)
        decided = np.asarray(fleet.step().decided)
        for t, model in enumerate(models):
            live[t] &= not decided[t]  # the view change wipes the detectors: stop before it
            if not live[t]:
                continue
            probed = (obs[t] >= 0) & alive[t][np.clip(obs[t], 0, None)] & alive[t][:, None]
            model.step(int(first_round[t]) + r, probed, lost[t])
            np.testing.assert_array_equal(np.asarray(fleet.state.fd_fired)[t], model.fired)
            np.testing.assert_array_equal(
                np.asarray(fleet.state.fire_round)[t][model.fired], model.fire_round[model.fired])
            compared[t] += 1
            fired_in |= {t} if model.fired.any() else set()
    faulty = {t for t, (slots, *_) in enumerate(TENANTS) if slots}
    assert fired_in == faulty and min(compared) >= 3, (fired_in, compared)
    assert not models[-1].fired.any() and compared[-1] == 16  # nobody faulty: never an edge


# -- (c) false reports stay under L, per tenant --------------------------------


def test_false_reports_are_carried_and_never_proposed_in_any_tenant(compiled):
    """The fleet's round without the view change that wipes the tallies: in a
    tenant's deciding round every healthy subject of a faulty observer carries
    that observer's ring bit in every cohort, and the cut is the faulty set."""
    tenants = (((2, 31), 1000, 0, 0, 1), ((11,), 1000, 0, 0, 2), ((), 0, 0, 0, 3))
    fleet = make_fleet(tenants)
    observers = np.asarray(fleet.state.obs_idx)  # [t, k, n]
    named = np.zeros((len(tenants), N), dtype=bool)
    for t, slot in pairs_of(tenants):
        named[t, slot] = True
    for t in range(len(tenants)):  # the precondition the traffic checks at set-up
        reports = link_model.false_reports(observers[t], np.nonzero(named[t])[0])
        assert reports[~named[t]].max(initial=0) < KNOBS[1]
    fleet.set_link_faults(pairs_of(tenants), **controls_of(tenants))
    masks = fleetm.fleet_edge_masks(fleet.cfg, fleet.state, fleet.faults)
    one_round = jax.jit(  # donate-ok: a test's own round
        lambda state, links: jax.vmap(
            lambda s, f, kn, m, lane: vcm._compute_round(
                fleetm._tenant_cfg(fleet.cfg, kn), s, f, m, links=lane)
        )(state, fleet.faults, fleet.knobs, masks, links))
    frozen_round = jax.jit(  # donate-ok: a test's own round, every tenant outside ``active``
        lambda state, links: fleetm._gated_round(
            fleet.cfg, state, (), fleet.faults, fleet.knobs, masks,
            active=jnp.zeros((len(tenants),), bool), links=links)[3:])
    state, links = fleet.state, fleet.links
    decided_at = {}
    for r in range(12):
        state, decided, winner, _, links = one_round(state, links)
        assert not (np.asarray(state.prop_mask).any(axis=1) & ~named).any(), "a healthy member was proposed"
        for t in np.nonzero(np.asarray(decided))[0]:
            if int(t) in decided_at:
                continue
            decided_at[int(t)] = r
            np.testing.assert_array_equal(np.asarray(winner)[t], named[t])
            bits = np.asarray(state.report_bits)[t]  # [c, n]
            false_edges = list(zip(*np.nonzero(named[t][observers[t]] & ~named[t][None, :])))
            assert len(false_edges) >= 8
            for ring, subject in false_edges:
                assert ((bits[:, subject] >> ring) & 1).all(), (t, ring, subject)
            tallies = np.array([bin(int(word)).count("1") for word in bits[0]])
            assert tallies[~named[t]].max() < KNOBS[1] <= KNOBS[0] <= tallies[named[t]].min()
    assert sorted(decided_at) == [0, 1]  # the tenant nobody is faulty in never decides
    # a frozen round hands the lane back as it went in
    commits, _, _, frozen = frozen_round(state, links)
    assert not np.asarray(commits).any()
    assert_same(leaves_of(frozen), leaves_of(links), "a frozen round's lane")


# -- (d) no lane is the program of before --------------------------------------


def _shapes(fleet):
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    masks = jax.eval_shape(fleetm.fleet_edge_masks, fleet.cfg, fleet.state, fleet.faults)
    lane = jax.eval_shape(lambda: LinkFaults.none(fleet.cfg, fleet.b))
    return {
        "gated_round": (lambda c, s, f, k, m, **kw: fleetm._gated_round(c, s, (), f, k, m, **kw), (masks,)),
        "step": (fleetm.fleet_step_gated_impl, (i32(3), masks)),
        "decision": (fleetm.fleet_run_to_decision_impl, (i32(),)),
        "wave": (lambda c, s, f, k, t, m, mc, **kw: fleetm.fleet_wave_impl(c, s, f, k, t, m, 4, mc, **kw),
                 (i32(fleet.b), i32(), i32(fleet.b))),
    }, lane


@pytest.mark.parametrize("program", ["gated_round", "step", "decision", "wave"])
def test_an_unset_lane_traces_the_program_of_no_lane(compiled, program):
    fleet = make_fleet(TENANTS[:2], warm=False)
    programs, lane = _shapes(fleet)
    impl, controls = programs[program]
    args = (fleet.state, fleet.faults, fleet.knobs, *controls)
    without = jax.make_jaxpr(lambda *a: impl(fleet.cfg, *a))(*args)
    unset = jax.make_jaxpr(lambda *a: impl(fleet.cfg, *a, links=None))(*args)
    assert str(without) == str(unset)
    with_lane = jax.make_jaxpr(lambda lane, *a: impl(fleet.cfg, *a, links=lane))(lane, *args)
    assert len(with_lane.out_avals) == len(without.out_avals) + len(lane)
    assert [aval.shape for aval in with_lane.out_avals[-len(lane):]] == [leaf.shape for leaf in lane]
    assert str(with_lane) != str(without)


def test_a_fleet_without_a_lane_dispatches_no_lane_program(compiled):
    fleet = make_fleet(TENANTS[:2], warm=False)
    fleet.stream_crash([(0, 3), (1, 4)])
    fleet.step()
    rounds, decided, _, _ = fleet.run_to_decision(max_steps=32)
    assert decided.all() and fleet.links is None
    assert "engine_link_probes_lost" not in fleet.metrics.counters
    assert "inject_link_faults" not in fleet.metrics.phase_timings.get("engine_dispatch", {})
    fetched = fleet.metrics.counters["engine_d2h_bytes"]
    fleet.run_until_membership([N - 1] * 2, **WAVE)
    bare = fleet.metrics.counters["engine_d2h_bytes"] - fetched
    fleet.set_link_faults([(0, 5)], 800)
    assert fleet.metrics.counters["engine_link_probes_lost"] == 0
    assert "inject_link_faults" in fleet.metrics.phase_timings["engine_dispatch"]
    assert "rapid_engine_link_probes_lost_total" in fleet.prometheus_text()
    fetched = fleet.metrics.counters["engine_d2h_bytes"]
    fleet.run_until_membership([N - 2, N - 1], **WAVE)
    # the lane's counts ride the verb's one fetch: 4 bytes a tenant more
    assert fleet.metrics.counters["engine_d2h_bytes"] - fetched == bare + 4 * fleet.b
    fleet.set_link_faults([])  # clear: the same call with no pairs
    assert fleet.links is None
    fetched = fleet.metrics.counters["engine_d2h_bytes"]
    fleet.run_until_membership([N - 2, N - 1], **WAVE)
    assert fleet.metrics.counters["engine_d2h_bytes"] - fetched == bare


# -- (e) the lane's gather lives where the masks live --------------------------


def _gathers_placed(jaxpr, inside=(), conds=None):
    """Where every ``gather`` of a jaxpr lies: ``"while"`` for a loop's body,
    ``(n, arm)`` for an arm of the program's ``n``-th conditional (in program
    order), outermost first; as ``tests/test_spans.py::_placed`` walks."""
    conds = [0] if conds is None else conds
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield inside
        if eqn.primitive.name == "cond":
            nth, conds[0] = conds[0], conds[0] + 1
            for arm, branch in enumerate(eqn.params["branches"]):
                yield from _gathers_placed(branch.jaxpr, (*inside, (nth, arm)), conds)
        elif eqn.primitive.name == "while":
            yield from _gathers_placed(eqn.params["body_jaxpr"].jaxpr, (*inside, "while"), conds)
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _gathers_placed(sub, inside, conds)


def test_the_wave_makes_the_lanes_gather_before_its_loop_and_after_a_commit_only(compiled):
    """The loss at every edge's observer reads the lane and the topology
    alone: the whole-wave loop gathers it before its rounds and again in the
    ``stale`` arm at the head of a round that follows a commit, beside the
    masks (whose own gather lies in the same two places), never once a round."""
    import collections

    fleet = make_fleet(TENANTS[:2], warm=False)
    programs, lane = _shapes(fleet)
    impl, controls = programs["wave"]
    args = (fleet.state, fleet.faults, fleet.knobs, *controls)
    bare = collections.Counter(_gathers_placed(
        jax.make_jaxpr(lambda *a: impl(fleet.cfg, *a))(*args).jaxpr))
    laned = collections.Counter(_gathers_placed(
        jax.make_jaxpr(lambda lane, *a: impl(fleet.cfg, *a, links=lane))(lane, *args).jaxpr))
    more = laned - bare
    assert not bare - laned and sum(more.values()) == 2
    before_the_loop, after_a_commit = sorted(more, key=len)
    assert before_the_loop == () and after_a_commit[0] == "while" and after_a_commit[1][1] == 1
    assert len(after_a_commit) == 2  # under the loop's own conditional, not under a round's
    # and it is the conditional that rebuilds the masks: the first of the body
    in_body = [at for at in laned if at[:1] == ("while",) and len(at) > 1]
    assert after_a_commit[1][0] == min(at[1][0] for at in in_body)
    # the step gathers once a round (its driver carries the masks alone)
    step, step_controls = programs["step"]
    step_args = (fleet.state, fleet.faults, fleet.knobs, *step_controls)
    count = lambda jaxpr: sum(1 for _ in _gathers_placed(jaxpr.jaxpr))  # noqa: E731
    assert count(jax.make_jaxpr(lambda lane, *a: step(fleet.cfg, *a, links=lane))(lane, *step_args)) == \
        count(jax.make_jaxpr(lambda *a: step(fleet.cfg, *a))(*step_args)) + 1


def test_a_wave_whose_tenants_commit_apart_counts_its_rebuilds(differential):
    # the engagement: the loop rebuilt masks and look-up after its commits
    # (all but a last one that ended the wave), not in each of its rounds
    counters = differential["run_until_membership"]["fleet"].metrics.counters
    assert 2 <= counters["engine_fleet_commit_rounds"] < counters["engine_fleet_wave_rounds"]
    assert counters["engine_edge_mask_builds"] in (
        counters["engine_fleet_commit_rounds"], counters["engine_fleet_commit_rounds"] - 1)


# -- (f) the setter --------------------------------------------------------------


def test_the_setter_checks_its_arguments_on_the_host(compiled):
    fleet = make_fleet(TENANTS[:2], warm=False)
    with pytest.raises(IndexError):
        fleet.set_link_faults([(0, N)])
    with pytest.raises(IndexError):
        fleet.set_link_faults([(2, 1)])
    with pytest.raises(ValueError, match="loss_permille"):
        fleet.set_link_faults([(0, 1)], [800, 1001])
    with pytest.raises(ValueError, match="on_rounds"):
        fleet.set_link_faults([(0, 1)], 1000, on_rounds=0, off_rounds=5)
    with pytest.raises(ValueError, match="seeds takes a scalar or one value"):
        fleet.set_link_faults([(0, 1)], seeds=[1, 2, 3])
    assert fleet.links is None
    assert "inject_link_faults" not in fleet.metrics.phase_timings.get("engine_dispatch", {})


def test_the_setter_is_one_upload_and_one_placement(compiled):
    fleet = make_fleet(TENANTS[:3], warm=False)
    uploaded, dispatched = (
        fleet.metrics.counters[name] for name in ("engine_h2d_bytes", "engine_dispatches"))
    fleet.set_link_faults(pairs_of(TENANTS[:3]), **controls_of(TENANTS[:3]))
    # four controls a tenant and two indices a pair, in one array
    assert fleet.metrics.counters["engine_h2d_bytes"] - uploaded == 4 * (4 * 3 + 2 * len(pairs_of(TENANTS[:3])))
    assert fleet.metrics.counters["engine_dispatches"] - dispatched == 1
    lane = leaves_of(fleet.links)
    expect = np.zeros((3, N), dtype=np.int32)
    for t, (slots, loss, *_) in enumerate(TENANTS[:3]):
        expect[t, list(slots)] = loss
    np.testing.assert_array_equal(lane["loss_permille"], expect)
    assert lane["seed"].tolist() == [11, 12, 2**32 - 5] and lane["seed"].dtype == np.uint32
    assert not lane["age"].any() and not lane["probes_lost"].any()
    # tenant by tenant it is the cluster's placement
    for t in range(3):
        vc = make_cluster(t, warm=False)
        set_on_cluster(vc, t)
        assert_same(leaves_of(fleet.links, t), leaves_of(vc.links), f"links[{t}]")
    # a scalar is every tenant's value, and the call replaces the lane that stood
    fleet.set_link_faults([(1, 9)], 500, on_rounds=4, off_rounds=2, seeds=7)
    lane = leaves_of(fleet.links)
    assert lane["loss_permille"].sum() == 500 and lane["loss_permille"][1, 9] == 500
    assert lane["on_rounds"].tolist() == [4] * 3 and lane["seed"].tolist() == [7] * 3


# -- (g) a mesh takes no lane ------------------------------------------------------


@pytest.mark.parametrize("factory", ["make_fleet_step", "make_fleet_wave"])
def test_the_mesh_factories_refuse_the_lane(compiled, factory):
    from rapid_tpu.parallel.mesh import make_mesh

    fleet = make_fleet(TENANTS[:2], warm=False)
    mesh = make_mesh(jax.devices()[:2], shape=(2, 1, 1))
    getattr(fleetm, factory)(fleet.cfg, mesh, links=None)  # a fleet that set none: as ever
    fleet.set_link_faults([(0, 1)])
    with pytest.raises(ValueError, match="link faults are off under a mesh"):
        getattr(fleetm, factory)(fleet.cfg, mesh, links=fleet.links)


# -- (h) what carries a set lane ---------------------------------------------------


def test_sync_and_a_device_copy_carry_a_set_lane(compiled, monkeypatch):
    fleet = make_fleet(TENANTS[:3])
    fleet.set_link_faults(pairs_of(TENANTS[:3]), **controls_of(TENANTS[:3]))
    waited, wait = [], jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda tree: (waited.append(tree), wait(tree))[1])
    fleet.sync()  # the placement is behind the barrier
    assert any(leaf is fleet.links.loss_permille for leaf in jax.tree_util.tree_leaves(waited))
    monkeypatch.undo()
    clone = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))  # donate-ok: a test's copy
    pristine = (clone(fleet.state), fleet.faults, fleet.links)
    targets = targets_of(TENANTS[:3])
    first = fleet.run_until_membership(targets, min_cuts=1, **WAVE)
    lost = fleet.metrics.counters["engine_link_probes_lost"]
    fleet.state, fleet.faults, fleet.links = clone(pristine[0]), pristine[1], pristine[2]
    again = fleet.run_until_membership(targets, min_cuts=1, **WAVE)
    for one, other in zip(first, again):
        np.testing.assert_array_equal(one, other)
    assert fleet.metrics.counters["engine_link_probes_lost"] == 2 * lost > 0
    np.testing.assert_array_equal(np.asarray(fleet.links.age), first[0])


def test_from_clusters_stacks_the_clusters_lanes(compiled):
    clusters = [make_cluster(t) for t in range(3)]
    set_on_cluster(clusters[0], 0)
    set_on_cluster(clusters[2], 2)
    clusters[2].step()  # a lane that has run: its clock and its count come along
    fleet = TenantFleet.from_clusters(clusters)
    assert fleet.metrics.counters["engine_link_probes_lost"] == 0
    for t, vc in enumerate(clusters):
        want = vc.links if vc.links is not None else LinkFaults.none(vc.cfg)
        assert_same(leaves_of(fleet.links, t), leaves_of(want), f"links[{t}]")
    assert np.asarray(fleet.links.age).tolist() == [0, 0, 1]
    # and it runs as the clusters do
    targets = targets_of(TENANTS[:3])
    rounds, cuts, resolved, _ = fleet.run_until_membership(targets, min_cuts=[1, 0, 1], **WAVE)
    for t, vc in enumerate(clusters):
        if t == 1:
            continue
        assert vc.run_until_membership(targets[t], min_cuts=1, **WAVE)[:3] == (
            rounds[t], cuts[t], resolved[t])
        assert_same(leaves_of(fleet.state, t), leaves_of(vc.state), f"state[{t}]")
        assert_same(leaves_of(fleet.links, t), leaves_of(vc.links), f"links[{t}]")
    assert TenantFleet.from_clusters([make_cluster(0, warm=False)] * 2).links is None


def test_a_fleet_checkpoint_round_trip_carries_a_set_lane(compiled, tmp_path):
    from rapid_tpu.serving import recovery
    from rapid_tpu.utils.checkpoint import load_link_faults

    fleet = make_fleet(TENANTS[:3])
    fleet.set_link_faults(pairs_of(TENANTS[:3]), **controls_of(TENANTS[:3]))
    fleet.step()
    path = recovery.write_checkpoint(tmp_path, fleet, 3, rounds_per_wave=4, depth=2)
    lane = load_link_faults(path)
    assert_same(leaves_of(lane), leaves_of(fleet.links), "links")
    supervisor, wave = recovery.resume(tmp_path)
    resumed = supervisor.target
    assert wave == 3
    assert isinstance(resumed, TenantFleet)
    assert_same(leaves_of(resumed.links), leaves_of(fleet.links), "links")
    targets = targets_of(TENANTS[:3])
    ours = resumed.run_until_membership(targets, min_cuts=1, **WAVE)
    theirs = fleet.run_until_membership(targets, min_cuts=1, **WAVE)
    for one, other in zip(ours, theirs):
        np.testing.assert_array_equal(one, other)
    assert_same(leaves_of(resumed.state), leaves_of(fleet.state), "state")
    assert_same(leaves_of(resumed.links), leaves_of(fleet.links), "links")
    # a fleet that set none resumes with none
    bare = make_fleet(TENANTS[:2], warm=False)
    recovery.write_checkpoint(tmp_path / "bare", bare, 0, rounds_per_wave=4, depth=2)
    assert recovery.resume(tmp_path / "bare")[0].target.links is None


# -- (i) the benchmark's configuration and its cell, small -------------------------


def held(*parts):
    with open(os.path.join(REPO, *parts), encoding="utf-8") as handle:
        return json.load(handle)


def test_the_configuration_is_the_sources(compiled):
    config = held("benchmarks", "configs", "paper-fleet-1k-gray.json")
    assert (config["tenants"], config["members"], config["slots"]) == (256, 1000, 1000)
    assert (config["k"], config["h"], config["l"]) == (10, 9, 3)  # the paper's, L included
    assert (config["fd_window"], config["fd_threshold"], config["fd_stagger_rounds"]) == (10, 4, 0)
    assert (config["cohorts"], config["cohort_assignment"], config["delivery_spread"]) == (8, "roundrobin", 2)
    assert config["deployment"] == "fleet" and len(config["source"]) <= 200
    assert "Fig. 10" in config["source"] and "{K,H,L}={10,9,3}" in config["source"]
    assert config["reduced"] == ["tenants"]
    assert sorted(config["assumed"]) == ["cohorts", "delivery_spread", "tenants"]
    assert any("exactly the faulty set" in line for line in config["guarantees"])
    assert any("no healthy member is evicted" in line for line in config["guarantees"])
    # the engine's shapes are paper-fleet-1k's, its control's
    fleet = held("benchmarks", "configs", "paper-fleet-1k.json")
    assert all(config[key] == fleet[key] for key in (
        "tenants", "members", "slots", "k", "h", "l", "cohorts", "delivery_spread"))
    traffic = held("benchmarks", "traffic", "ingress80.json")
    assert traffic["kind"] == "fleet_link_faults" and traffic["faulty_share"] == 0.01
    assert traffic["ingress_loss_permille"] == 800
    assert (traffic["on_rounds"], traffic["off_rounds"]) == (0, 0)
    assert (traffic["plan_cycle"], traffic["arrival_seed"], traffic["resolve"]) == (8, 7, "until_membership")
    bench = held("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "paper-fleet-1k-gray")
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    cell = next(w for w in bench["workloads"] if w["name"] == "paper-fleet-1k-gray.ingress80")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("paper-fleet-1k-gray", "ingress80", 1)


class _Context:
    """What ``benchmarks/harness.py`` hands a generator, without its clocks."""

    def __init__(self, config, traffic, seed):
        import contextlib

        self.config, self.traffic, self.seed = config, traffic, seed
        self.platform, self.seconds, self.run = "cpu", 0.0, {}
        self.span = lambda name: contextlib.nullcontext()
        self.window = lambda target: _OneCycle()


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


def small_twin(seed: int, fault=None, max_steps=None):
    """The cell at 6 tenants of 300 members (3 faulty each), two plans."""
    from benchmarks import control_fleet_link
    from benchmarks.generators import fleet_link_faults
    from benchmarks.targets_fleet_link import LinkFleetTarget

    config = dict(
        held("benchmarks", "configs", "paper-fleet-1k-gray.json"), tenants=6, members=300, slots=300,
        cohorts=4)
    traffic = dict(held("benchmarks", "traffic", "ingress80.json"), plan_cycle=2)

    def build(*args):
        target = LinkFleetTarget(*args)
        if max_steps is not None:  # a control never resolves: a short budget
            target.MAX_STEPS = max_steps
        if fault is not None:
            control_fleet_link.FAULTS[fault](target)
        return target

    fleet_link_faults.LinkFleetTarget = build
    try:
        return fleet_link_faults.run(_Context(config, traffic, seed))
    finally:
        fleet_link_faults.LinkFleetTarget = LinkFleetTarget


@pytest.mark.parametrize("seed", [7001, 2**31 + 11])
def test_a_small_twin_of_the_cell_is_correct(compiled, seed):
    record = small_twin(seed)
    assert record["attempted"] == 2 and record["failed"] == 0
    assert membership_model.failures(record["checks"]) == 0, record["checks"]
    assert record["view_changes"] >= 2 * 6 and sorted(record["commit_plan"]) == [0, 1]
    assert all(rounds < 40 for rounds in record["commit_rounds"])
    # tenants end in different rounds: the wave runs to the slowest
    assert record["tenant_rounds_useful"] < record["tenant_rounds_total"] == 6 * record["rounds"]


@pytest.mark.parametrize("fault,broken", [
    ("lose_fault", "crashed_in_view"), ("deafen_healthy", "healthy_evicted")])
def test_a_control_of_the_small_twin_is_not_correct(compiled, fault, broken):
    record = small_twin(7001, fault=fault, max_steps=40)
    checks = record["checks"]
    assert record["failed"] == record["attempted"] == 2
    assert checks[broken] >= 6 and checks["unresolved"] == 6  # in every tenant
    assert membership_model.failures(checks) >= 2
