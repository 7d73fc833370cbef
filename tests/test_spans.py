"""One span vocabulary inside the program.

Device: every scope of ``ENGINE_SCOPES`` is in the op-name metadata of the
lowered programs that trace it, and both arms of the round's conditionals
carry their own name. Host: every driver operation, the injection path
included, is a ``rapid:<phase>`` span in the profiler's trace with a rising
``seq``, and a streamed wave's enqueues and the fetch that retires it share a
``wave``. The lowered texts are traced here (no compile); the traced drive
runs in a process of its own, once for the module, because the programs it
compiles must stay out of this session (tier-1 ends within 1 % of
``vm.max_map_count``).
"""

import hashlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from rapid_tpu.ops.rings import view_change_bucket
from rapid_tpu.utils import profiling
from rapid_tpu.utils.dispatch import ENGINE_DISPATCH_PHASES, ENGINE_SCOPES, scope

REPO = Path(__file__).resolve().parent.parent

ROUND = {"edge_masks", "fd_tick", "deliver", "deliver_skip", "cut_detection",
         "invalidation", "tally", "classic", "view_change"}
#: Arms that return their operands trace no operation, so no op name can
#: carry them: the names are in the code (and in the vocabulary) for the day
#: the compiler or a later change gives those arms work of their own.
IDENTITY_ARMS = {"invalidation_skip", "classic_skip", "view_keep"}
#: The drivers' round programs by the names their level-0 jits carry; a
#: level adds its suffix. The cases below are generated from the drivers' own
#: tables (``_ROUND_PROGRAMS``, ``_FLEET_PROGRAMS``): one for each verb and
#: observer count of ``VirtualCluster`` and ``TenantFleet``.
LEVELS = ("", "_telem", "_trace")
CLUSTER_VERBS = {"step": "engine_step_carried", "decision": "run_to_decision",
                 "wave": "run_until_membership"}
FLEET_VERBS = {"step": "fleet_step", "decision": "fleet_run_to_decision", "wave": "fleet_wave"}
EXPECTED = {
    name + suffix: ROUND | ({"observers"} if level else set())
    | ({"loop_result"} if name in ("run_until_membership", "fleet_wave") else set())
    for name in (*CLUSTER_VERBS.values(), *FLEET_VERBS.values())
    for level, suffix in enumerate(LEVELS)
}
EXPECTED.update({
    "engine_step": ROUND,
    "edge_masks_build": {"edge_masks"},
    "fleet_edge_masks": {"edge_masks"},
    "mesh_fleet_step": ROUND,
    "mesh_fleet_wave": ROUND,
    "mesh_step": ROUND,
    "engine_step_trace": ROUND | {"observers"},
    "sync_checksum": {"sync_checksum"},
    "predecessor_of_keys": {"join_predecessors"},
})
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
#: The fixture keeps a driver program's jaxpr beside its lowering, under
#: this prefix: op-name paths say under WHICH arm an operation lies, not under
#: which conditional (the whole-wave loops have two), and carry no shapes.
JAXPR = "jaxpr:"


def _paths(lowered) -> set:
    """The op-name paths of a lowered program's debug locations."""
    return {p for p in re.findall(r'"([^"\n]*)"', lowered.as_text(debug_info=True)) if "/" in p}


def _scopes(paths) -> set:
    found = set()
    for path in paths:
        for part in path.split("/"):
            while part not in ENGINE_SCOPES and _WRAPPED.match(part):
                part = _WRAPPED.match(part).group(1)  # vmap(fd_tick) -> fd_tick
            if part in ENGINE_SCOPES:
                found.add(part)
    return found


@pytest.fixture(scope="module")
def lowered():
    from rapid_tpu.models import virtual_cluster as vcm
    from rapid_tpu.ops.rings import predecessor_of_keys
    from rapid_tpu.parallel.mesh import make_mesh, make_sharded_step, sharded_program
    from rapid_tpu.tenancy import fleet as fleetm

    kw = dict(n_slots=32, k=3, h=3, l=1, cohorts=2, fd_threshold=2,
              delivery_spread=1, concurrent_coordinators=2)
    observers = ({}, {"telemetry": True}, {"telemetry": True, "trace": 4})
    clusters = [vcm.VirtualCluster.create(28, **kw, **obs) for obs in observers]
    fleets = [
        fleetm.TenantFleet.create(
            2, 28, n_slots=32, k=3, cohorts=2, knobs=[(3, 1, 2)] * 2, delivery_spread=1, **obs)
        for obs in observers
    ]
    vc, traced, fleet = clusters[0], clusters[2], fleets[0]
    i32, s = jnp.int32, vc.state
    idx = jnp.arange(28, 30)
    # the carried masks as shapes: nothing is compiled or run here
    masks = jax.eval_shape(vcm.edge_masks_build, vc.cfg, s, vc.faults)
    fleet_masks = jax.eval_shape(
        fleetm.fleet_edge_masks, fleet.cfg, fleet.state, fleet.faults)
    mesh = make_mesh(jax.devices()[:4], shape=(1, 4))  # cluster-10m's layout
    mesh3d = make_mesh(jax.devices()[:8], shape=(2, 2, 2))
    out = {}
    # every (verb, observer count) of both drivers, as the driver's _advance
    # hands the arguments over: carried pytrees, faults (and knobs), controls
    per_tenant = jnp.full((2,), 28, i32), jnp.ones((2,), i32)
    for level, (one, many) in enumerate(zip(clusters, fleets)):
        carried = [t for t in (one.state, one.telem, one.trace_ring) if t is not None]
        for verb, controls in (("step", (masks,)), ("decision", (i32(16),)),
                               ("wave", (i32(28), i32(16), 4, i32(1)))):
            program = vcm._ROUND_PROGRAMS[verb][level].trace(
                one.cfg, *carried, one.faults, *controls)
            out[CLUSTER_VERBS[verb] + LEVELS[level]] = program.lower()
            out[JAXPR + CLUSTER_VERBS[verb] + LEVELS[level]] = program.jaxpr.jaxpr
        carried = [t for t in (many.state, many.telem, many.trace_ring) if t is not None]
        for verb, controls in (("step", (jnp.zeros((3,), i32), fleet_masks)), ("decision", (i32(16),)),
                               ("wave", (per_tenant[0], i32(16), 4, per_tenant[1]))):
            program = fleetm._FLEET_PROGRAMS[verb][level].trace(
                many.cfg, *carried, many.faults, many.knobs, *controls)
            out[FLEET_VERBS[verb] + LEVELS[level]] = program.lower()
            out[JAXPR + FLEET_VERBS[verb] + LEVELS[level]] = program.jaxpr.jaxpr
    out.update({
        "engine_step": vcm.engine_step.lower(vc.cfg, s, vc.faults),
        "edge_masks_build": vcm.edge_masks_build.lower(vc.cfg, s, vc.faults),
        "fleet_edge_masks": fleetm.fleet_edge_masks.lower(
            fleet.cfg, fleet.state, fleet.faults),
        "mesh_fleet_step": fleetm.make_fleet_step(fleet.cfg, mesh3d).lower(
            fleet.state, fleet.faults, fleet.knobs),
        "mesh_fleet_wave": fleetm.make_fleet_wave(fleet.cfg, mesh3d, max_cuts=4).lower(
            fleet.state, fleet.faults, fleet.knobs, per_tenant[0], i32(16), per_tenant[1]),
        "mesh_step": make_sharded_step(vc.cfg, mesh).lower(s, vc.faults),
        "mesh_run_to_decision": sharded_program("decision", vc.cfg, mesh).lower(
            s, vc.faults, i32(16)),
        JAXPR + "mesh_run_to_decision": sharded_program("decision", vc.cfg, mesh).trace(
            s, vc.faults, i32(16)).jaxpr.jaxpr,
        JAXPR + "mesh_fleet_wave": fleetm.make_fleet_wave(fleet.cfg, mesh3d, max_cuts=4).trace(
            fleet.state, fleet.faults, fleet.knobs, per_tenant[0], i32(16), per_tenant[1]
        ).jaxpr.jaxpr,
        # the mesh's step body with both observers riding
        "engine_step_trace": jax.jit(vcm.engine_step_impl, static_argnums=(0,)).lower(
            traced.cfg, traced.state, traced.telem, traced.trace_ring, traced.faults),
        "sync_checksum": vcm.sync_checksum.lower(s, vc.faults),
        "predecessor_of_keys": predecessor_of_keys.lower(
            s.ring_pos, s.ring_perm, s.alive, idx),
    })
    return out


@pytest.mark.parametrize("program", sorted(EXPECTED))
def test_lowered_program_carries_its_scopes(lowered, program):
    assert _scopes(_paths(lowered[program])) == EXPECTED[program]


def test_every_registered_scope_is_traced_somewhere_or_is_an_identity_arm():
    assert set().union(*EXPECTED.values()) | IDENTITY_ARMS == set(ENGINE_SCOPES)
    assert len(set(ENGINE_SCOPES)) == len(ENGINE_SCOPES)


@pytest.mark.parametrize(
    "program", ["run_until_membership", "engine_step", "engine_step_carried"])
@pytest.mark.parametrize("arms", [
    ("deliver", "deliver_skip"), ("invalidation",), ("classic",), ("view_change",)])
def test_each_arm_of_a_conditional_carries_its_own_name(lowered, program, arms):
    paths = _paths(lowered[program])
    branches = {}
    for path in paths:
        match = re.search(r"cond/(branch_\d+_fun)/(\w+)", path)
        if match and match.group(2) in arms:
            branches.setdefault(match.group(2), set()).add(match.group(1))
    # every arm that traces an operation is named, and no branch has two names
    assert set(branches) == set(arms)
    assert all(len(found) == 1 for found in branches.values())
    assert len({next(iter(found)) for found in branches.values()}) == len(arms)


def test_under_an_unnamed_vmap_both_arms_are_traced_into_the_fleet_step(lowered):
    # An unnamed vmap turns a per-cluster cond into a select: both arms run,
    # and the names ride inside the transform's brackets. That is the mesh's
    # lockstep step, for the round's own conditionals and for the view
    # change: it has no conditional at all (a predicate reduced over the
    # 'tenant' axis would be a collective across it).
    paths = _paths(lowered["mesh_fleet_step"])
    for arm in ("view_change", "deliver", "deliver_skip", "invalidation", "classic"):
        assert any(re.search(r"(?:vmap\(|/)%s\)?(?:/|$)" % arm, p) for p in paths), arm
    assert not any("cond/" in p for p in paths)
    # the meshless step's view change is vmapped inside its one arm
    assert any("vmap(view_change)" in p for p in _paths(lowered["fleet_step"]))


GATED_ARMS = ("deliver", "invalidation", "classic")


@pytest.mark.parametrize("program", [
    name + suffix for name in ("fleet_step", "fleet_run_to_decision", "fleet_wave")
    for suffix in LEVELS])
def test_the_meshless_fleet_programs_keep_the_rounds_conditionals(lowered, program):
    # under the named batch axis the round's three conditionals stay
    # conditionals (taken when some tenant needs the arm): every operation
    # traced under their scopes lies in an arm, each scope in one arm only
    paths = _paths(lowered[program])
    for arm in GATED_ARMS:
        under = {p for p in paths if re.search(r"(?:^|/)%s(?:/|$)" % arm, p)}
        assert under, arm
        branches = {re.search(r"(?:^|/)cond/(branch_\d+_fun)/%s(?:/|$)" % arm, p) for p in under}
        assert None not in branches, (arm, sorted(under)[:3])
        assert len({m.group(1) for m in branches}) == 1, arm
        assert not any("vmap(%s)" % arm in p for p in paths), arm


@pytest.mark.parametrize("program", ["mesh_fleet_step", "mesh_fleet_wave"])
def test_the_programs_that_name_no_batch_axis_keep_the_select(lowered, program):
    # the mesh's step and its lockstep wave (the drivers' wave is gated
    # since PR 33): both arms of every conditional of the round, for every
    # tenant, in every round
    paths = _paths(lowered[program])
    for arm in GATED_ARMS:
        under = {p for p in paths if re.search(r"(?:vmap\(|/)%s\)?(?:/|$)" % arm, p)}
        assert under and not any("cond/" in p for p in under), arm


@pytest.mark.parametrize("program", ["fleet_step", "fleet_wave"])
def test_the_meshless_fleet_programs_gate_the_view_change_on_one_conditional(lowered, program):
    # the step and the whole-wave loop the drivers dispatch: the vmapped view
    # change lies under one arm of one scalar conditional taken outside the
    # vmap, and nowhere else
    paths = _paths(lowered[program])
    view_change = {p for p in paths if "view_change" in p}
    arms = {re.search(r"(?:^|/)cond/(branch_\d+_fun)/vmap\(view_change\)/", p) for p in view_change}
    assert view_change and None not in arms
    assert len({m.group(1) for m in arms}) == 1
    # the other arm returns its operand: it traces no operation (IDENTITY_ARMS);
    # the conditionals inside the vmap are the round's own
    # (a path that does not start at the program is the body of a jitted jnp
    # helper, named from the arm that called it: no conditional of its own)
    outside = [
        p for p in paths
        if p.startswith("jit(") and "cond/" in p and "/vmap(" not in p.split("cond/")[0]
    ]
    # (the first conditional of a path: the view change's arm holds one of
    # its own, inside the vmap, since PR 50: ``ring_alive``'s overflow gate)
    assert {m.group(1) for m in arms} == {
        m.group(1) for p in outside if (m := re.search(r"cond/(branch_\d+_fun)/", p))
    }


def _placed(jaxpr, inside=(), path="", conds=None):
    """``(where, op-name path)`` of every equation of a jaxpr, sub-jaxprs
    walked in program order. ``where`` is the control flow an equation lies
    under, outermost first: ``"while"`` for a loop's body and ``(n, arm)``
    for arm ``arm`` of the program's ``n``-th conditional (counted in that
    order). A ``jit`` wrapper and a loop's predicate add nothing to it."""
    conds = [0] if conds is None else conds
    for eqn in jaxpr.eqns:
        here = "/".join(part for part in (path, str(eqn.source_info.name_stack)) if part)
        yield inside, here
        if eqn.primitive.name == "cond":
            nth, conds[0] = conds[0], conds[0] + 1
            for arm, branch in enumerate(eqn.params["branches"]):
                yield from _placed(branch.jaxpr, (*inside, (nth, arm)), here, conds)
        elif eqn.primitive.name == "while":
            yield from _placed(eqn.params["body_jaxpr"].jaxpr, (*inside, "while"), here, conds)
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _placed(sub, inside, here, conds)


@pytest.mark.parametrize("level", LEVELS)
@pytest.mark.parametrize("program", ["fleet_wave", "run_until_membership"])
def test_a_whole_wave_loop_builds_its_masks_where_a_round_will_read_them(lowered, program, level):
    # The masks are built at the point of first use after the state they
    # depend on changed, never at the point of change: the loop may end with
    # the commit, and then nothing reads a rebuild.
    placed = list(_placed(lowered[JAXPR + program + level]))

    def where(name):
        return {inside for inside, path in placed if name in _scopes({path})}

    builds, view_change = where("edge_masks"), where("view_change")
    # the view change: the taken arm of one conditional of the loop's body
    # (and under it the two arms of its own one conditional: ``ring_alive``
    # by update, or gathered whole)
    (loop, (cut, taken)), = {at[:2] for at in view_change}
    assert (loop, taken) == ("while", 1)
    (own,) = {at[2][0] for at in view_change if len(at) > 2}
    assert view_change == {("while", (cut, 1)), ("while", (cut, 1), (own, 0)), ("while", (cut, 1), (own, 1))}
    if program == "fleet_wave":
        # one build before the loop, one in the taken arm of a conditional of
        # its own at the head of the body (no conditional of the round comes
        # before it), which is not the view change's
        head = min(at[1][0] for at, _ in placed if len(at) > 1 and at[0] == "while" and at[1] != "while")
        assert builds == {(), ("while", (head, 1))} and head != cut
    else:
        # one build a convergence: in the outer body, outside any conditional
        # and outside the inner round loop, and none before the outer loop
        assert builds == {("while",)}
        assert ("while", "while") in {inside for inside, _ in placed}
    lockstep = {p for p in _paths(lowered["mesh_fleet_wave"]) if "edge_masks" in p}
    assert lockstep and not any("cond/" in p for p in lockstep)


@pytest.mark.parametrize("program", ["engine_step_carried", "fleet_step"])
def test_the_meshless_step_builds_its_masks_in_the_cuts_taken_arm_only(lowered, program):
    # the step the drivers dispatch carries its per-edge masks: the one build
    # it still traces lies under the arm of the view-change conditional that
    # also holds the view change, and a round that commits nothing runs none
    paths = _paths(lowered[program])
    arm = r"(?:^|/)cond/(branch_\d+_fun)/(?:vmap\()?%s\)?/"
    builds = {p for p in paths if "edge_masks" in p}
    build_arms = {re.search(arm % "edge_masks", p) for p in builds}
    assert builds and None not in build_arms
    view_arms = {m.group(1) for p in paths if (m := re.search(arm % "view_change", p))}
    assert {m.group(1) for m in build_arms} == view_arms and len(view_arms) == 1
    # where the mesh's step, the parent's program, builds them in every round
    assert any("edge_masks" in p and "cond/" not in p for p in _paths(lowered["mesh_step"]))


@pytest.mark.parametrize("program", ["edge_masks_build", "fleet_edge_masks"])
def test_the_build_program_carries_the_scope_at_top_level(lowered, program):
    paths = {p for p in _paths(lowered[program]) if p.startswith("jit(")}  # not the source files
    assert paths and not any("cond/" in p for p in paths)
    assert all(re.match(r"jit\(\w+\)/(?:vmap\()?edge_masks\)?/", p) for p in paths)


def _equations_placed(jaxpr, path=""):
    """``(op-name path, equation)`` of every equation of a jaxpr, inner
    jaxprs walked; a conditional's arms read ``cond/branch_<i>`` as they do
    in a lowering's debug locations."""
    for eqn in jaxpr.eqns:
        here = "/".join(part for part in (path, str(eqn.source_info.name_stack)) if part)
        yield here, eqn
        if eqn.primitive.name == "cond":
            for arm, branch in enumerate(eqn.params["branches"]):
                yield from _equations_placed(branch.jaxpr, f"{here}/cond/branch_{arm}")
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations_placed(sub, here)


def _view_change_facts(jaxpr, k, n):
    """Of the equations traced under ``view_change``: the paths of the
    conditionals, of the gathers that look a bool up for every position of
    every ring (``alive[ring_perm]``: ``[..., n]`` bools, ``k * n`` or more of
    them; the update's own look-ups are a bucket long), and ``(path, updates'
    shape)`` of the scatters."""
    conditionals, gathers, scatters = [], [], []
    for path, eqn in _equations_placed(jaxpr):
        if "view_change" not in _scopes({path}):
            continue
        if eqn.primitive.name == "cond":
            conditionals.append(path)
        out = eqn.outvars[0].aval if eqn.outvars else None
        if (eqn.primitive.name == "gather" and out.dtype == jnp.bool_
                and out.shape[-1] == n and out.size >= k * n):
            gathers.append(path)
        if eqn.primitive.name == "scatter":
            scatters.append((path, eqn.invars[2].aval.shape))
    return conditionals, gathers, scatters


_ONE_DEVICE_PROGRAMS = [
    name + suffix for name in (*CLUSTER_VERBS.values(), *FLEET_VERBS.values())
    for suffix in LEVELS]


@pytest.mark.parametrize("program", _ONE_DEVICE_PROGRAMS)
def test_a_one_device_view_change_gathers_liveness_only_in_its_overflow_arm(lowered, program):
    """Every one-device round program: the view change holds ONE conditional
    of its own (a real ``cond``, under the fleet's ``vmap`` too: named, its
    predicate is reduced over the tenants; a select would run the gather for
    everybody, always), and the only look-up of a bool for every position of
    every ring, ``alive[ring_perm]``, lies in that conditional's taken arm:
    a cut that fits the bucket gathers nothing of ring length."""
    conditionals, gathers, _ = _view_change_facts(lowered[JAXPR + program], k=3, n=32)
    assert len(conditionals) == 1, conditionals
    (gate,) = conditionals
    assert gathers and all(p.startswith(gate + "/cond/branch_1") for p in gathers), gathers
    if program.startswith("fleet"):
        assert "vmap(view_change)" in gate


@pytest.mark.parametrize("program", _ONE_DEVICE_PROGRAMS)
def test_a_one_device_view_change_scatters_a_ring_table_only_in_its_overflow_arm(lowered, program):
    """PR 52: the same conditional picks the observer table's form. Its
    bounded arm scatters by no ``perm``: two updates a bucket long, ``[k, B]``
    bools into ``ring_alive`` and ``2B`` ring indices a ring (``[k, 2B]`` here) into the
    table the state holds (the cut's slots and their predecessors); the
    N-update scatter a ring, the walk's, lies in the taken arm alone (where,
    under the fleet's named ``vmap``, the bounded form is computed beside it
    for the tenants whose cut fits). Nothing of the view change scatters
    outside the conditional."""
    k, n, bucket = 3, 32, view_change_bucket(32)
    (gate,), _, scatters = _view_change_facts(lowered[JAXPR + program], k=k, n=n)
    assert all(path.startswith(gate + "/cond/branch_") for path, _ in scatters), scatters
    arms = [
        sorted(shape[-2:] for path, shape in scatters if path.startswith(f"{gate}/cond/branch_{arm}"))
        for arm in (0, 1)
    ]
    # 32 slots: all rings at once; as traced, before the dead-code pass takes
    # the walk's other two outputs out (tests/test_view_change_tables.py)
    bounded, by_perm = [(k, bucket), (k, 2 * bucket)], [(k, n)] * 3
    assert arms[0] == bounded, arms
    assert arms[1] == sorted(by_perm + (bounded if program.startswith("fleet") else [])), arms


@pytest.mark.parametrize("program", ["mesh_run_to_decision", "mesh_fleet_wave"])
def test_a_mesh_view_change_keeps_the_whole_gather_and_no_conditional(lowered, program):
    # the programs whose node axis (or tenant axis) is sharded trace the
    # dense form alone: the operations they always ran, one more result
    conditionals, gathers, scatters = _view_change_facts(lowered[JAXPR + program], k=3, n=32)
    assert conditionals == [] and len(gathers) == 1
    # and the walk's N-update scatters (as traced: of its three outputs the
    # compiler keeps one), no update a bucket long: PR 52's repair is the
    # one-device programs'
    assert [shape[-2:] for _, shape in scatters] == [(3, 32)] * 3


#: First sixteen hex digits of the SHA-256 of ``lowered.as_text()`` at commit
#: f450378 (the parent of PR 28), at this module's tiny shapes: the programs
#: of the cells the carried masks bypass (churn5's fused wave, crash10's
#: fleet decision, cluster-10m's meshed decision) and the two mesh steps.
#: ``fleet_run_to_decision`` is PR 30's: it names its batch axis, and the
#: round's conditionals in it are conditionals; the other four name none.
#: ``run_until_membership`` is PR 34's: it builds its masks at the head of
#: each convergence and no longer in the cut's arm. All five were re-taken
#: at PR 37: each holds the view change, whose ring walk now scans words that
#: carry the neighbour's slot (``ops/rings.py::_from_perm_single``) and no
#: longer gathers ``perm`` by the positions it scanned; nothing else in them
#: moved (``edge_masks_build`` and the other programs without a view change
#: lower to the parent's text). And again at PR 42: the state holds no
#: predecessor table, so each loses that output, its scatter and the walk's
#: prefix-max; the programs without a view change lower to the parent's text.
#: And at PR 44, for one hunk alone: each holds the classic arm, whose
#: coordinator rule now pools the counts of cohorts that announced the same
#: cut (``value_of``, a ``[c]`` word made under ``tally``; a ``[c, c]`` compare
#: and a sum in the arm). Before that hunk the five, and the 29 other programs
#: of this module's fixture, lowered to PR 42's text with the consensus-path
#: counts (``paths=None``) in the tree.
#: At PR 45 the two one-device programs were re-taken: their ``invalidation``
#: arm compacts the subjects in flux and keeps the dense loop as its overflow
#: arm (``ops/cut_detection.py``). The three mesh programs were NOT: they
#: trace the dense loop alone (``dense_arms=True`` now) and lower to PR
#: 44's text, as do the four programs of the fixture without the arm.
#: At PR 46 all five were re-taken for the mask build alone: each holds
#: ``_edge_masks``, which now gathers once an edge (the packed ``rx_block``
#: words and the ``active`` bit in one table; ``tests/test_edge_masks.py``
#: holds its outputs to the two-gather body). Nothing else in them moved.
#: At PR 49 all five were re-taken for their signature alone: the state has
#: one more lane, ``ring_pos``, which a round hands through unread (only the
#: join placement reads it), so each text gains an operand and a result.
#: At PR 50 all five were re-taken: the state has one more lane,
#: ``ring_alive`` (liveness by ring position), which the view change's walk
#: reads and its commit writes. In the two one-device programs the commit
#: flips the cut's own positions under a conditional of its own, the whole
#: gather its other arm; the three mesh programs gather it whole, the
#: operation their walk always made, and hand it out as one more result.
#: At PR 52 the two one-device programs were re-taken: the same conditional
#: now picks the observer table's form too (its bounded arm repairs
#: ``inval_obs`` at the cut's slots and their predecessors, the walk moved
#: into its taken arm). The three mesh programs were NOT: ``dense_arms``
#: traces the rebuild alone and they lower to PR 50's text, as do all ten
#: programs of the fixture without a one-device view change
#: (``tools/program_digests.py``).
#: A PR that means to change one of them replaces its digest with the one
#: the failure prints.
PARENT_PROGRAMS = {
    "run_until_membership": "bb1a55e58f1f5012",
    "fleet_run_to_decision": "573303abb6d84759",
    "mesh_run_to_decision": "368d054b5d8a823b",
    "mesh_step": "07bc72c6e6905c1c",
    "mesh_fleet_step": "d12c1f4b2ca30c05",
}


@pytest.mark.parametrize("program", sorted(PARENT_PROGRAMS))
def test_a_program_the_carried_masks_bypass_lowers_to_the_parents_text(lowered, program):
    text = lowered[program].as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_PROGRAMS[program]


def test_scope_names_keep_clear_of_the_hlo_gates_needles():
    from rapid_tpu.parallel import hlo_facts

    for name in ENGINE_SCOPES:
        assert hlo_facts.source_of(name) == "other", name
        assert hlo_facts.classify_location("jit(f)/" + name + "/add") == "prologue", name


def test_benchmarks_scope_list_is_the_programs():
    with open(REPO / "benchmarks" / "scopes.json", encoding="utf-8") as handle:
        assert json.load(handle)["scopes"] == list(ENGINE_SCOPES)


@pytest.mark.parametrize("kind", ["scope", "phase"])
def test_an_unregistered_name_raises_at_write_time(kind):
    from rapid_tpu.models.virtual_cluster import VirtualCluster

    if kind == "scope":
        with pytest.raises(ValueError, match="unregistered engine scope 'fd_tik'"):
            scope("fd_tik")
        assert "fd_tick" in ENGINE_SCOPES
        return
    vc = VirtualCluster.create(12, n_slots=16, k=3, h=3, l=1)
    with pytest.raises(ValueError, match="unregistered engine dispatch phase 'inject_crsh'"):
        with vc._dispatch("inject_crsh"):
            pass
    assert {"inject_crash", "inject_join_admit", "inject_join_place"} <= ENGINE_DISPATCH_PHASES
    assert {"inject_link_faults", "inject_partition"} <= ENGINE_DISPATCH_PHASES


def test_annotate_is_a_trace_annotation_with_tags_and_free_without_a_trace():
    import jax

    span = profiling.annotate("rapid:sync", seq=3, wave=None)
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span:  # no trace running: nothing is recorded, nothing raises
        pass


def test_one_construction_site_for_trace_annotations():
    sites = [
        str(path.relative_to(REPO)) for path in (REPO / "rapid_tpu").rglob("*.py")
        if re.search(r"TraceAnnotation\(", path.read_text(encoding="utf-8"))
    ]
    assert sites == ["rapid_tpu/utils/profiling.py"]


# -- the host spans in a real trace ---------------------------------------------

_DRIVE = r"""
import glob, json, sys
import jax
from rapid_tpu.models.virtual_cluster import VirtualCluster
from rapid_tpu.serving.stream import StreamDriver, StreamWave
from rapid_tpu.utils import profiling

def build():
    vc = VirtualCluster.create(28, n_slots=40, k=3, h=3, l=1, cohorts=2, fd_threshold=2)
    vc.assign_cohorts_roundrobin()
    vc.sync()
    return vc

def commit(vc, victim, joiner, check):
    vc.crash([victim])
    vc.inject_join_wave([joiner], check_admissible=check)
    vc.sync()
    return vc.run_until_membership(28, max_steps=64, max_cuts=4, min_cuts=1)

def stream(vc, first):
    # No opportunistic reaping: every wave is then retired by a blocking fetch,
    # whatever the machine's speed.
    driver = StreamDriver(vc, rounds_per_wave=2, depth=2, ticket_ready=lambda index, ticket: False)
    for i in range(4):
        driver.submit(StreamWave(crash=(first + i,), join=()))
    driver.drain()

warm, checked, unchecked, streamed = build(), build(), build(), build()
commit(warm, 1, 30, True); commit(warm, 2, 31, False); stream(warm, 5)  # compile everything first
out = {}
for name, work in (
    ("checked", lambda: commit(checked, 1, 30, True)),
    ("unchecked", lambda: commit(unchecked, 1, 30, False)),
    ("streamed", lambda: stream(streamed, 5)),
):
    where = sys.argv[1] + "/" + name
    with profiling.trace(where):
        work()
    data = jax.profiler.ProfileData.from_file(glob.glob(where + "/**/*.xplane.pb", recursive=True)[0])
    spans = [
        (e.start_ns, e.name, {k: v for k, v in dict(e.stats).items() if not k.startswith("_")})
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name.startswith("rapid:")
    ]
    out[name] = [[n, s] for _, n, s in sorted(spans, key=lambda t: t[0])]
print("SPANS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    done = subprocess.run(
        [sys.executable, "-c", _DRIVE, str(tmp_path_factory.mktemp("spans"))],
        cwd=str(REPO), env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-4000:]
    line = next(l for l in done.stdout.splitlines() if l.startswith("SPANS "))
    return json.loads(line[len("SPANS "):])


def test_a_commit_leaves_its_host_spans_with_rising_seq(spans):
    names = [name for name, _ in spans["checked"]]
    assert names == [
        "rapid:inject_crash", "rapid:inject_join_admit", "rapid:inject_join_place",
        "rapid:sync", "rapid:run_until_membership",
    ]
    seqs = [tags["seq"] for _, tags in spans["checked"]]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert seqs == list(range(seqs[0], seqs[0] + 5))  # the driver's own operation count


def test_an_unchecked_join_wave_opens_no_admit_span(spans):
    names = [name for name, _ in spans["unchecked"]]
    assert "rapid:inject_join_admit" not in names
    assert names == [
        "rapid:inject_crash", "rapid:inject_join_place", "rapid:sync", "rapid:run_until_membership",
    ]


def test_a_waves_enqueues_and_the_fetch_that_retires_it_share_a_wave(spans):
    enqueued, fetched = {}, {}
    for name, tags in spans["streamed"]:
        if name == "rapid:stream_enqueue":
            enqueued.setdefault(tags["wave"], []).append(tags["seq"])
        elif name == "rapid:stream_fetch" and "wave" in tags:
            fetched[tags["wave"]] = tags["seq"]
    assert sorted(enqueued) == [0, 1, 2, 3] and all(len(v) == 2 for v in enqueued.values())
    assert sorted(fetched) == [0, 1, 2, 3]  # every wave is retired by a fetch that names it
    for wave, seq in fetched.items():
        assert seq > max(enqueued[wave])
    # depth 2: wave 0 is retired only after wave 1 was enqueued
    assert fetched[0] > max(enqueued[1])
    # the constructor's and the drain's own fetches carry no wave
    assert any(name == "rapid:stream_fetch" and "wave" not in tags for name, tags in spans["streamed"])
    # the crash before each wave's rounds is a span too
    assert sum(name == "rapid:inject_crash" for name, _ in spans["streamed"]) == 4


@pytest.mark.parametrize("drive", ["checked", "unchecked", "streamed"])
def test_the_spans_of_one_membership_change_share_a_change_tag(spans, drive):
    """``change`` is the dispatch journal's id (``utils/dispatch.py``): the
    spans of one view change carry the same one, those outside carry 0, so a
    trace and ``journal_snapshot()`` join on ``seq`` and group on ``change``."""
    assert all("change" in tags for _, tags in spans[drive])
    if drive != "streamed":
        (change,) = {tags["change"] for _, tags in spans[drive]}
        assert change > 0  # one commit: inject ... run_until_membership
        return
    by_wave, without = {}, []
    for name, tags in spans["streamed"]:
        if "wave" in tags:
            by_wave.setdefault(tags["wave"], set()).add(tags["change"])
        elif name == "rapid:inject_crash":
            without.append(tags["change"])  # a wave's crash: the next wave's id
        else:
            assert name == "rapid:stream_fetch" and tags["change"] == 0  # constructor, drain
    changes = [change for wave in sorted(by_wave) for change in by_wave[wave]]
    assert len(changes) == 4 and all(len(ids) == 1 for ids in by_wave.values())  # one id a wave
    assert changes == sorted(changes) and changes[0] > 0 and len(set(changes)) == 4
    assert without == changes  # each wave's injection carries the wave's id
