"""The engine state holds the one ring table the engine reads, ``obs_idx``, and
a view change therefore computes only that one (PR 42): ``EngineState`` has no
predecessor table, ``ops/rings.py`` still gives both (``RingTopology.subj_idx``
stays, tested against the sorting oracle in ``tests/test_ops_rings.py``), and
the compiler takes out what the state no longer asks for: the second ring-index
scatter a ring and the walk's prefix-max.

(a) the mechanism, read off the compiled program at both schedules of the rings;
(b) a differential: the engine's step against the same step with its view change
    re-deriving the rings by SORTING the keys (``ring_topology``), over seeded
    crash, join and mixed schedules, every lane and every event of every round;
(c) an archive the parent wrote (it holds the table) loads, the table ignored.
"""

import re
from collections import Counter
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rapid_tpu.models import virtual_cluster as vcm
from rapid_tpu.models.state import (
    LANE_SPECS, NARROWABLE_LANES, EngineConfig, EngineState, FaultInputs, initial_state)
from rapid_tpu.ops import rings
from rapid_tpu.parallel.mesh import PARTITION_RULES
from rapid_tpu.tenancy import fleet as fleetm
from rapid_tpu.utils import checkpoint
from tests.test_ops_rings import _equations, _primitives


@pytest.fixture(scope="module", autouse=True)
def _give_back_what_the_module_compiled():
    yield
    # Tier-1 runs near the process's limit of memory maps (the verify notes).
    jax.clear_caches()


# -- (a) one ring-index scatter a ring ---------------------------------------


def _view_change_programs(n):
    cfg = EngineConfig(n=n, k=10, h=9, l=4, c=4)
    keys = jax.ShapeDtypeStruct((cfg.k, n), jnp.uint32)
    ids = jax.ShapeDtypeStruct((n,), jnp.uint32)
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_)
    state = jax.eval_shape(
        lambda kh, kl, ih, il, a: initial_state(cfg, kh, kl, ih, il, a), keys, keys, ids, ids, mask)
    traced = jax.jit(vcm.apply_view_change_impl, static_argnums=(0,)).trace(cfg, state, mask)
    return traced.jaxpr.jaxpr, traced.lower().compile().as_text()


def _live_primitives(jaxpr):
    """Primitive -> count over what the outputs need (jax's own dead-code
    pass, as lowering runs it), inner jaxprs included."""
    from jax._src.interpreters import partial_eval as pe

    live, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    return Counter(_primitives(live))


def _assert_same_leaves(ours, theirs, where):
    for field in ours._fields:
        x, y = getattr(ours, field), getattr(theirs, field)
        assert x.dtype == y.dtype, (where, field)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=f"{where}: {field}")


def _arm_scatters(compiled):
    """``[repair's, rebuild's]``: ``(element type, dims)`` of the scatters of a
    compiled program that lie in either arm of the view change's own
    conditional (the false arm first), by the op-name path each carries."""
    arms = [[], []]
    for line in compiled.splitlines():
        scatter = re.search(r"= (\w+)\[([\d,]*)\][^=]* scatter\(", line)
        arm = re.search(r"view_change\)?/cond/branch_(\d)_fun/", line)
        if scatter and arm:
            arms[int(arm.group(1))].append(
                (scatter.group(1), tuple(map(int, scatter.group(2).split(",")))))
    return [sorted(arm) for arm in arms]


def _view_change_gate(jaxpr):
    """The arms ``(repair, rebuild)`` of the one ``cond`` of a view change's
    jaxpr, dead code taken out as lowering does."""
    from jax._src.interpreters import partial_eval as pe

    live, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    (gate,) = [eqn for eqn in _equations(live) if eqn.primitive.name == "cond"]
    repair, rebuild = gate.params["branches"]  # the false arm first
    return repair.jaxpr, rebuild.jaxpr


@pytest.mark.parametrize("n", [1000, rings.RING_AT_A_TIME_SLOTS], ids=["batched", "rings_in_turn"])
def test_the_compiled_view_change_scatters_one_ring_table(n):
    """Turned round at PR 52: the view change's bounded arm scatters NO ring
    table by ``perm`` any more. It makes two bounded updates, the cut's
    positions flipped in ``ring_alive`` (``[K, B]`` bools, PR 50) and the
    observer table repaired at the cut's slots and their predecessors (``2B``
    updates of ring indices a ring, under the schedule the ring length picks,
    where the walk made N); the overflow arm holds what the whole commit held, the lane's
    gather and the one N-update scatter a ring."""
    k, bucket = 10, rings.view_change_bucket(n)
    jaxpr, compiled = _view_change_programs(n)
    assert (" while(" in compiled) == (n >= rings.RING_AT_A_TIME_SLOTS)  # the form the length picks
    repair, rebuild = _arm_scatters(compiled)
    # a table's K rings in one scatter (batched), or one in the loop's body (in
    # turn), in either arm; the lane's flip is one scatter at every length
    one_at_a_time = n >= rings.RING_AT_A_TIME_SLOTS
    table = ("s32", (n,) if one_at_a_time else (k, n))
    assert repair == [("pred", (k, n)), table]
    assert rebuild == [table]
    # and what jax hands the compiler: the updates' sizes, and the dead half of
    # the rebuild's walk gone before XLA sees it, through the inner jit, the
    # vmap and the lax.map alike
    _, pieces = rings.ring_walk_pieces(n)
    repair, rebuild = _view_change_gate(jaxpr)
    updates = sorted(
        eqn.invars[2].aval.shape for eqn in _equations(repair) if eqn.primitive.name == "scatter")
    assert updates == sorted([(k, bucket), (2 * bucket,) if one_at_a_time else (k, 2 * bucket)]), updates
    found = Counter(_primitives(repair))
    assert found["cummin"] == found["cummax"] == pieces  # succ' and the slot at pred', by position
    assert found["sort"] == 0
    by_perm = [eqn for eqn in _equations(rebuild) if eqn.primitive.name == "scatter"]
    assert [eqn.invars[2].aval.shape[-1] for eqn in by_perm] == [n]  # N updates a ring
    found = Counter(_primitives(rebuild))
    assert found["cummin"] == pieces and found["cummax"] == found["sort"] == 0
    # the ops still give both tables to a caller that takes both
    both = jax.make_jaxpr(lambda p, a: rings.ring_topology_from_perm(p, a)[:2])(
        jax.ShapeDtypeStruct((10, n), jnp.int32), jax.ShapeDtypeStruct((n,), jnp.bool_))
    found = _live_primitives(both.jaxpr)
    assert found["scatter"] == 2 and found["cummax"] == found["cummin"] == pieces


def test_a_dense_only_view_change_scatters_the_one_ring_table_it_did():
    """``dense_arms=True`` (a mesh's programs, the two unnamed-``vmap`` fleet
    programs): no conditional, the one N-update scatter a ring and no update
    of a bucket's size."""
    n = 1000
    cfg = EngineConfig(n=n, k=10, h=9, l=4, c=4)
    keys, ids = jax.ShapeDtypeStruct((cfg.k, n), jnp.uint32), jax.ShapeDtypeStruct((n,), jnp.uint32)
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_)
    state = jax.eval_shape(
        lambda kh, kl, ih, il, a: initial_state(cfg, kh, kl, ih, il, a), keys, keys, ids, ids, mask)
    traced = jax.jit(
        lambda s, w: vcm.apply_view_change_impl(cfg, s, w, dense_arms=True)).trace(state, mask)
    found = _live_primitives(traced.jaxpr.jaxpr)
    _, pieces = rings.ring_walk_pieces(n)
    assert found["cond"] == 0 and found["scatter"] == 1
    assert found["cummin"] == pieces and found["cummax"] == 0


@pytest.mark.parametrize("driver", ["cluster", "fleet"])
def test_the_whole_wave_loop_scatters_one_ring_table(driver):
    """There the view change sits under a ``while`` and a ``cond``, where jax's
    pass leaves the walk whole and the compiler's own has to take the half out:
    of the rebuild's arm, that is (PR 52); the repair's arm reads both halves
    of the walk's scans and scatters one bounded update of ring indices."""
    i32, kw = jnp.int32, dict(n_slots=32, k=3, cohorts=2, delivery_spread=1)
    if driver == "cluster":
        vc = vcm.VirtualCluster.create(28, h=3, l=1, fd_threshold=2, **kw)
        traced = vcm._ROUND_PROGRAMS["wave"][0].trace(
            vc.cfg, vc.state, vc.faults, i32(28), i32(16), 4, i32(1))
    else:
        fleet = fleetm.TenantFleet.create(2, 28, knobs=[(3, 1, 2)] * 2, **kw)
        traced = fleetm._FLEET_PROGRAMS["wave"][0].trace(
            fleet.cfg, fleet.state, fleet.faults, fleet.knobs,
            jnp.full((2,), 28, i32), i32(16), 4, jnp.ones((2,), i32))
    repair, rebuild = _arm_scatters(traced.lower().compile().as_text())
    # one table in each arm: the repair's bounded update, the rebuild's by
    # ``perm`` (the pred scatter is ``ring_alive``'s update); under the fleet's
    # named ``vmap`` the taken arm computes both forms and selects a tenant at
    # a time (``utils/dispatch.cond_across``)
    assert [kind for kind, _ in repair] == ["pred", "s32"]
    assert [kind for kind, _ in rebuild] == (
        ["s32"] if driver == "cluster" else ["pred", "s32", "s32"])


def test_the_state_names_no_predecessor_table():
    assert "subj_idx" not in EngineState._fields
    assert "subj_idx" not in LANE_SPECS and "subj_idx" not in NARROWABLE_LANES
    assert not any(re.fullmatch(rule, "subj_idx") for rule, _ in PARTITION_RULES)
    assert "subj_idx" in rings.RingTopology._fields  # the ops keep theirs


# -- (b) the step against the step that sorts ---------------------------------


def _step_that_sorts(cfg, state, faults):
    """``engine_step_impl`` with the view change's rings from
    ``ring_topology``, the argsort over (dead, key): the oracle the sort-free
    walk, and since PR 52 the repair of the table the state holds, is held
    to, here through the whole round. ``dense_arms``: the twin rebuilds at
    every commit, which is where the engine calls the walk by that name."""

    def by_sorting(_perm, alive, _ring_alive):
        return rings.ring_topology(state.key_hi, state.key_lo, alive)

    with mock.patch.object(vcm, "ring_topology_from_perm", by_sorting):
        return vcm.engine_step_impl(cfg, state, faults, dense_arms=True)


_ORACLE_STEP = jax.jit(_step_that_sorts, static_argnums=(0,))  # donate-ok: the twin's state is compared afterwards

KW = dict(n_slots=64, cohorts=4, fd_threshold=2, delivery_spread=1)


def _schedule(kind, seed):
    """``[(round, verb, slots)]``: members 0..43 stand, slots 44..63 are free."""
    rng = np.random.default_rng(seed)
    members, free = rng.permutation(44), 44 + rng.permutation(20)
    crash = [(0, "crash", members[:3].tolist()), (9, "crash", members[3:5].tolist())]
    join = [(0, "join", free[:4].tolist()), (8, "join", free[4:7].tolist())]
    mixed = [(0, "crash", members[:2].tolist()), (1, "join", free[:3].tolist()),
             (10, "join", free[3:5].tolist()), (11, "crash", members[2:4].tolist())]
    return {"crash": crash, "join": join, "mixed": mixed}[kind]


@pytest.mark.parametrize("kind,seed", [("crash", 11), ("join", 23), ("mixed", 37)])
def test_every_lane_and_event_equals_the_sorting_oracles(kind, seed):
    real = vcm.VirtualCluster.create(44, seed=seed, **KW)
    twin = vcm.VirtualCluster.create(44, seed=seed, **KW)
    for vc in (real, twin):
        vc.assign_cohorts_roundrobin()
    schedule, cuts = _schedule(kind, seed), 0
    for rnd in range(24):
        for _, verb, slots in (e for e in schedule if e[0] == rnd):
            for vc in (real, twin):
                vc.crash(slots) if verb == "crash" else vc.inject_join_wave(slots)
        twin.state, want = _ORACLE_STEP(twin.cfg, twin.state, twin.faults)
        got = real.step()
        _assert_same_leaves(got, want, f"events of round {rnd}")
        _assert_same_leaves(real.state, twin.state, f"state after round {rnd}")
        cuts += int(got.decided)
    joined = sum(len(s) for _, verb, s in schedule if verb == "join")
    crashed = sum(len(s) for _, verb, s in schedule if verb == "crash")
    assert cuts >= 2 and real.membership_size == 44 + joined - crashed


# -- (c) the parent's archives -----------------------------------------------


def _parents_archive(path, cfg, entries, key, table):
    """Seal ``entries`` plus the table the parent's state held, as its writer did."""
    assert key not in entries
    entries = {**checkpoint._cfg_entries(cfg), **entries, key: table}
    checkpoint._atomic_write(path, checkpoint._seal(checkpoint._npz_bytes(entries)))


@pytest.mark.parametrize("writer", ["save_engine_state", "save_serving_state"])
def test_an_archive_with_the_parents_table_loads(tmp_path, writer):
    vc = vcm.VirtualCluster.create(40, n_slots=48, fd_threshold=2, seed=5)
    vc.crash([3, 17])
    vc.step()
    topo = rings.ring_topology_from_perm(vc.state.ring_perm, vc.state.alive)
    table = np.asarray(topo.subj_idx.astype(vc.state.obs_idx.dtype))
    path = tmp_path / "parent.npz"
    if writer == "save_engine_state":
        entries = {f: np.asarray(v) for f, v in vc.state._asdict().items() if f != "ring_perm"}
        _parents_archive(path, vc.cfg, entries, "subj_idx", table)
        cfg, state = checkpoint.load_engine_state(path)
    else:
        entries = {f"state__{f}": np.asarray(v) for f, v in vc.state._asdict().items()}
        entries.update({f"faults__{f}": np.asarray(v) for f, v in vc.faults._asdict().items()})
        entries["__meta__"] = np.frombuffer(b"{}", dtype=np.uint8)
        _parents_archive(path, vc.cfg, entries, "state__subj_idx", table)
        cfg, state, faults, knobs, meta = checkpoint.load_serving_state(path)
        assert isinstance(faults, FaultInputs) and knobs is None and meta == {}
    assert cfg == vc.cfg and state._fields == EngineState._fields
    _assert_same_leaves(state, vc.state, writer)
    # and it goes on to the decision the live cluster reaches
    resumed = vcm.VirtualCluster(cfg, state)
    resumed.crash([3, 17])
    assert resumed.run_until_converged()[0] == vc.run_until_converged()[0]
    np.testing.assert_array_equal(resumed.alive_mask, vc.alive_mask)
