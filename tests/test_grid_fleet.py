"""The paper's K/H/L grid as tenants of one fleet, held to its plain reference.

Tenants of one ``TenantFleet`` with DIFFERENT watermarks and different numbers
of concurrent crashes, under a network that delivers a report to different
receivers in different rounds (``delivery_spread`` 8), so that the cohorts of
one tenant announce different cuts by delivery order alone, some tenants lose
their fast quorum and the classic round decides them, inside the fleet's
whole-wave loop and under its any-tenant gates. Held against

(a) ``benchmarks/detector_model.py`` (the cut detector replayed round by round
    in numpy from the observer table and the delays, no code shared with the
    engine): per tenant the path, the decision's round, the cuts, the first
    cut's size and the count of dissenting cohorts (the ``tl_dissent`` lane);
(b) the same tenants as separate ``VirtualCluster``s, leaf for leaf and lane
    for lane (the fleet's parity bar, where the classic decision comes from
    cohorts that announced different cuts, through ``run_until_membership``);
(c) ``delivery_delays`` against the bits ``_deliver_alerts`` delivers, round
    by round;
(d) the lane's plumbing, and its absence from every telemetry-off program.

The drives run in ONE process of their own (``python tests/test_grid_fleet.py``
prints one JSON record a drive): what they compile stays out of this session,
which ends within 1 % of vm.max_map_count.
"""

import itertools
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]

#: 16 tenants of 240 members in 4 cohorts of 60: the fast quorum of 181 needs
#: all four, so ONE cohort that announces another cut sends a tenant to the
#: classic round. A corner of the grid that conflicts, and its control (9, 3).
MEMBERS, COHORTS, SPREAD, FALLBACK, FD = 240, 4, 8, 8, 3
WATERMARKS, CRASHES = [(6, 4), (6, 3), (7, 4), (9, 3)], [2, 4, 8, 16]
KNOBS = [(h, l, FD) for (h, l), _ in itertools.product(WATERMARKS, CRASHES)]
VICTIMS = [f for _, f in itertools.product(WATERMARKS, CRASHES)]
#: draw -> what the plain reference finds in it (classic tenants, tenants
#: whose first cut is partial, tenants whose coordinator's quorum ties):
#: found by running the reference alone over the first draws.
DRAWS = {0: (2, 1, 0), 1: (5, 0, 0), 4: (0, 0, 0), 6: (3, 1, 1)}
#: (delivery_spread, delivery_prob_permille, config_epoch)
SCHEDULES = [(0, 1000, 0), (2, 1000, 0), (8, 1000, 0), (8, 1000, 5), (4, 300, 0), (4, 300, 2)]


def drive_draw(draw: int) -> dict:
    """One draw through the fleet, the reference and sixteen clusters."""
    import jax

    from benchmarks import detector_model
    from rapid_tpu.models.virtual_cluster import VirtualCluster
    from rapid_tpu.tenancy import TenantFleet

    seeds = list(range(100 * draw, 100 * draw + len(KNOBS)))
    engine = dict(delivery_spread=SPREAD, fallback_rounds=FALLBACK, telemetry=1)
    fleet = TenantFleet.create(
        len(KNOBS), MEMBERS, n_slots=MEMBERS, k=10, cohorts=COHORTS, seeds=seeds,
        knobs=KNOBS, **engine)
    rng = np.random.default_rng(draw)
    victims = [np.sort(rng.choice(MEMBERS, size=f, replace=False)) for f in VICTIMS]
    observers, cohort_of = np.asarray(fleet.state.obs_idx), np.arange(MEMBERS) % COHORTS
    expected = [
        detector_model.expectation(
            members=MEMBERS, observers=observers[t], cohort_of=cohort_of, victims=victims[t],
            delays=delays, high=KNOBS[t][0], low=KNOBS[t][1], fd_threshold=FD,
            fallback_rounds=FALLBACK)
        for t, delays in enumerate(fleet.delivery_delays(victims))
    ]
    targets = MEMBERS - np.asarray(VICTIMS)
    fleet.stream_crash([(t, slot) for t, slots in enumerate(victims) for slot in slots])
    rounds, cuts, resolved, sizes = fleet.run_until_membership(
        targets, max_steps=192, max_cuts=4, min_cuts=1)
    fleet.sync()
    alive = np.asarray(fleet.state.alive)
    differ = []
    for t, (h, l, fd) in enumerate(KNOBS):
        vc = VirtualCluster.create(
            MEMBERS, n_slots=MEMBERS, k=10, h=h, l=l, cohorts=COHORTS, fd_threshold=fd,
            seed=seeds[t], **engine)
        vc.assign_cohorts_roundrobin()
        vc.crash(victims[t])
        alone = vc.run_until_membership(int(targets[t]), max_steps=192, max_cuts=4, min_cuts=1)
        vc.sync()
        if (alone[0], alone[1], alone[2], list(alone[3])) != (
                int(rounds[t]), int(cuts[t]), bool(resolved[t]), [s for s in sizes[t] if s >= 0]):
            differ.append((t, "outcome"))
        for name, tree, theirs in (("state", fleet.tenant_state(t), vc.state),
                                   ("lanes", jax.tree_util.tree_map(lambda x: x[t], fleet.telem), vc.telem)):
            differ += [
                (t, f"{name}.{field}") for field, left, right in zip(tree._fields, tree, theirs)
                if not np.array_equal(np.asarray(left), np.asarray(right))
            ]
    gates = fleet.metrics.counters
    return {
        "drive": f"draw{draw}",
        "tenants": [
            {
                "expected": {
                    "path": e["path"], "round": e["round"], "whole": e["whole"],
                    "dissent": e["dissent"], "first_cut": None if e["cut"] is None else int(e["cut"].sum()),
                    "announced_cuts": len({c.tobytes() for c, at in zip(e["cuts"], e["announced"]) if at >= 0}),
                },
                "rounds": int(rounds[t]), "cuts": int(cuts[t]), "resolved": bool(resolved[t]),
                "sizes": [int(s) for s in sizes[t]],
                "out": sorted(np.flatnonzero(~alive[t]).tolist()) == sorted(victims[t].tolist()),
                **{lane: fleet.tenant_activity[t][lane]
                   for lane in ("decisions_fast", "decisions_classic", "dissent", "conflict_rounds")},
            }
            for t, e in enumerate(expected)
        ],
        "leaves_that_differ": differ,
        "wave_rounds": gates["engine_fleet_wave_rounds"],
        "classic_rounds": gates["engine_fleet_classic_rounds"],
        "scraped": 'rapid_engine_activity_dissent_total{node="tenant-fleet/16x240",tenant="1"} '
                   f'{fleet.tenant_activity[1]["dissent"]}' in fleet.prometheus_text(),
    }


def drive_schedule(spread: int, permille: int, epoch: int) -> dict:
    """``delivery_delays`` against what ``_deliver_alerts`` delivers, every
    round from the firing to past the last arrival, every edge of a cluster
    fired (in two different rounds, so that the age is the edge's own)."""
    import jax.numpy as jnp

    from rapid_tpu.models import virtual_cluster as vcm

    vc = vcm.VirtualCluster.create(
        60, n_slots=64, k=10, cohorts=6, delivery_spread=spread, delivery_prob_permille=permille,
        seed=3)
    cfg, n = vc.cfg, vc.cfg.n
    fired = np.where(np.arange(n)[:, None] % 2 == 0, 1, 3).astype(np.int32) * np.ones((1, cfg.k), np.int32)
    state = vc.state._replace(
        config_epoch=jnp.asarray(epoch, vc.state.config_epoch.dtype),
        fire_round=jnp.asarray(fired, vc.state.fire_round.dtype))
    delays = np.asarray(vcm.delivery_delays(cfg, state.config_epoch, np.arange(n)))  # [c, n, k]
    _, blocked_rows = vcm._edge_masks(cfg, state, vc.faults)
    wrong = 0
    for round_ in range(spread + 6):
        state = state._replace(round_idx=jnp.asarray(round_, state.round_idx.dtype))
        bits = np.asarray(vcm._deliver_alerts(cfg, state, state.fire_round, blocked_rows))  # [c, n]
        delivered = (bits[:, :, None] >> np.arange(cfg.k)) & 1
        wrong += int((delivered != (round_ - fired[None] >= delays)).sum())
    through_driver = np.asarray(vc.delivery_delays([5, 9]))
    return {
        "drive": f"schedule{spread}-{permille}-{epoch}", "wrong": wrong, "shape": list(delays.shape),
        "least": int(delays.min()), "most": int(delays.max()), "late_share": float((delays > 0).mean()),
        "driver_reads_its_own_epoch": bool(
            (through_driver == np.asarray(vcm.delivery_delays(cfg, vc.state.config_epoch, [5, 9]))).all()),
    }


def drive() -> None:
    import jax

    for draw in DRAWS:
        print(json.dumps(drive_draw(draw)), flush=True)
        jax.clear_caches()
    for schedule in SCHEDULES:
        print(json.dumps(drive_schedule(*schedule)), flush=True)


@pytest.fixture(scope="module")
def drives():
    done = subprocess.run(
        [sys.executable, str(Path(__file__).resolve())], capture_output=True, text=True,
        cwd=str(REPO), env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)},
        timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    records = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
    return {record["drive"]: record for record in records}


# -- (a) the fleet against the plain reference -----------------------------------


@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_every_tenant_takes_the_path_the_detector_model_gives(drives, draw):
    for t, tenant in enumerate(drives[f"draw{draw}"]["tenants"]):
        expected, where = tenant["expected"], f"draw {draw}, tenant {t} (H, L, F = {KNOBS[t][:2]}, {VICTIMS[t]})"
        decided = {"fast": tenant["decisions_fast"], "classic": tenant["decisions_classic"]}
        assert tenant["resolved"] and tenant["out"], where
        assert decided["fast"] + decided["classic"] == tenant["cuts"], where
        assert tenant["sizes"][tenant["cuts"] - 1] == MEMBERS - VICTIMS[t], where
        if expected["first_cut"] is not None:  # else two values tie: the rule allows either
            assert tenant["sizes"][0] == MEMBERS - expected["first_cut"], where
        if expected["whole"]:
            # one view change, by the reference's path, in the reference's
            # round, with the reference's count of cohorts that said otherwise
            assert tenant["cuts"] == 1 and decided[expected["path"]] == 1, where
            assert tenant["rounds"] == expected["round"] + 1, where
            assert tenant["dissent"] == expected["dissent"], where
        else:  # the first cut is partial (or a tie's, of either size): that one by the reference's path, then more
            tied = expected["first_cut"] is None
            assert decided[expected["path"]] >= 1 and tenant["rounds"] >= expected["round"] + 2 - tied, where
            assert tenant["cuts"] >= 2 - tied, where
            assert tenant["dissent"] >= (expected["dissent"] or 0), where
        # a classic decision stood undecided through the recovery delay
        if expected["path"] == "classic":
            assert tenant["conflict_rounds"] >= FALLBACK, where


def test_the_draws_hold_every_case_the_check_knows():
    # (from the record of what the reference found; the drive holds the engine to it)
    assert {found[0] > 0 for found in DRAWS.values()} == {True, False}
    assert any(found[1] for found in DRAWS.values()) and any(found[2] for found in DRAWS.values())


@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_the_reference_finds_in_a_draw_what_the_record_says(drives, draw):
    expected = [tenant["expected"] for tenant in drives[f"draw{draw}"]["tenants"]]
    classic = [e for e in expected if e["path"] == "classic"]
    assert (len(classic), sum(not e["whole"] for e in expected),
            sum(e["first_cut"] is None for e in expected)) == DRAWS[draw]
    # a classic decision here comes from cohorts that announced DIFFERENT
    # cuts, never from silent ones: every cohort has announced by then
    assert all(e["announced_cuts"] >= 2 for e in classic)
    # the control's watermarks wait for every report: (9, 3) never conflicts
    assert all(e["path"] == "fast" and e["dissent"] == 0 for e in expected[12:])


@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_the_classic_arm_runs_for_the_fleet_when_some_tenant_needs_it(drives, draw):
    record = drives[f"draw{draw}"]
    slowest = max(tenant["rounds"] for tenant in record["tenants"])
    assert record["wave_rounds"] == slowest  # the loop ends with its slowest tenant
    attempts = sum(tenant["decisions_classic"] for tenant in record["tenants"])
    if attempts:  # nobody is cut off here, so every attempt decides; tenants may share a round
        assert 1 <= record["classic_rounds"] <= attempts
    else:
        assert record["classic_rounds"] == 0
    assert record["scraped"]


def test_the_dissent_lane_is_zero_where_every_cohort_agrees(drives):
    quiet = drives["draw4"]["tenants"]
    assert all(t["dissent"] == 0 and t["decisions_fast"] == 1 and t["decisions_classic"] == 0 for t in quiet)
    # and counts cohorts, not rounds, where they do not
    loud = [t for t in drives["draw1"]["tenants"] if t["decisions_classic"]]
    assert loud and all(0 < t["dissent"] < COHORTS <= t["conflict_rounds"] for t in loud)


# -- (b) the fleet against its tenants as separate clusters ----------------------


@pytest.mark.parametrize("draw", sorted(DRAWS))
def test_the_fleet_is_its_tenants_as_separate_clusters_leaf_for_leaf(drives, draw):
    assert drives[f"draw{draw}"]["leaves_that_differ"] == []


# -- (c) the delays as data -------------------------------------------------------


@pytest.mark.parametrize("spread,permille,epoch", SCHEDULES)
def test_delivery_delays_is_the_schedule_deliver_alerts_follows(drives, spread, permille, epoch):
    record = drives[f"schedule{spread}-{permille}-{epoch}"]
    assert record["wrong"] == 0 and record["shape"] == [6, 64, 10]
    assert record["driver_reads_its_own_epoch"]
    if spread == 0:
        assert (record["least"], record["most"]) == (0, 0)
    elif permille >= 1000:  # uniform on [0, spread]
        assert (record["least"], record["most"]) == (0, spread)
        assert abs(record["late_share"] - spread / (spread + 1)) < 0.03
    else:  # late with probability p, then uniform on [1, spread]
        assert (record["least"], record["most"]) == (0, spread)
        assert abs(record["late_share"] - permille / 1000) < 0.03


def test_an_epoch_draws_a_schedule_of_its_own(drives):
    # (the records hold summaries; two epochs of one geometry differ in them)
    assert drives["schedule8-1000-0"]["late_share"] != drives["schedule8-1000-5"]["late_share"]


# -- (d) the lane goes where every lane goes, and nowhere else --------------------


def test_the_lane_is_in_every_list_a_lane_is_in():
    from rapid_tpu.models.state import TELEMETRY_LANE_SPECS, TelemetryLanes
    from rapid_tpu.parallel.mesh import PARTITION_RULES, match_partition_rules
    from rapid_tpu.utils import engine_telemetry, exposition

    assert TELEMETRY_LANE_SPECS["tl_dissent"] == () and "tl_dissent" in TelemetryLanes._fields
    assert list(TELEMETRY_LANE_SPECS) == list(TelemetryLanes._fields)
    fields = engine_telemetry.TELEMETRY_DIGEST_FIELDS
    assert fields.index("dissent") == fields.index("conflict_rounds") + 1
    assert "dissent" in exposition._ENGINE_ACTIVITY_COUNTERS
    assert engine_telemetry.zero_activity_summary(8, 2)["dissent"] == 0
    pooled = engine_telemetry.aggregate_activity(
        [dict(engine_telemetry.zero_activity_summary(8, 2), dissent=d) for d in (2, 3)], 8, 2)
    assert pooled["dissent"] == 5
    placed = match_partition_rules(PARTITION_RULES, TelemetryLanes._fields)
    assert placed["tl_dissent"] == placed["tl_conflict_rounds"] == ()  # replicated, as the scalars are


def _observer_equations(jaxpr) -> int:
    import jax

    found = 0
    for eqn in jaxpr.eqns:
        found += "observers" in str(eqn.source_info.name_stack)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _observer_equations(sub)
    return found


@pytest.mark.parametrize("program", ["step", "decision", "wave", "fleet step", "fleet wave"])
def test_a_telemetry_off_program_traces_nothing_of_the_observers(program):
    """The lane is written under the ``observers`` scope, which a program
    traced without a ``TelemetryLanes`` pytree does not hold one equation of:
    no cell but the grid's sets ``telemetry``, so no other cell's program
    changed (``tools/program_digests.py`` shows the same on the lowered text)."""
    import jax
    import jax.numpy as jnp

    from rapid_tpu.models import virtual_cluster as vcm
    from rapid_tpu.models.state import TELEMETRY_LANE_SPECS, initial_telemetry
    from rapid_tpu.tenancy import fleet as fl
    from test_partition import _shapes

    tenants = 3
    cfg = vcm.VirtualCluster.create(28, n_slots=32, k=10, cohorts=2, telemetry=1, seed=1).cfg
    state, faults = _shapes(cfg)
    lanes = jax.eval_shape(lambda: initial_telemetry(cfg))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    if program.startswith("fleet"):
        stack = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda leaf: jax.ShapeDtypeStruct((tenants, *leaf.shape), leaf.dtype), tree)
        state, faults, lanes = stack(state), stack(faults), stack(lanes)
        knobs = fl.TenantKnobs(*(jax.ShapeDtypeStruct((tenants,), jnp.int32) for _ in fl.TenantKnobs._fields))
        per_tenant = jax.ShapeDtypeStruct((tenants,), jnp.int32)
        if program == "fleet step":
            masks = jax.eval_shape(lambda s, f: fl.fleet_edge_masks_impl(cfg, s, f), state, faults)
            impl, controls = fl.fleet_step_gated_impl, (knobs, jax.ShapeDtypeStruct((3,), jnp.int32), masks)
        else:
            impl = lambda c, s, *rest: fl.fleet_wave_impl(c, s, *rest[:-1], 4, rest[-1])  # noqa: E731
            controls = (knobs, per_tenant, i32, per_tenant)
    else:
        impl, controls = {
            "step": (vcm.engine_step_impl, ()),
            "decision": (vcm.run_to_decision_impl, (i32,)),
            "wave": (lambda c, s, *rest: vcm.run_until_membership_impl(c, s, *rest[:-1], 4, rest[-1]),
                     (i32, i32, i32)),
        }[program]
    off = jax.make_jaxpr(lambda s, f, *c: impl(cfg, s, f, *c))(state, faults, *controls)
    on = jax.make_jaxpr(lambda s, t, f, *c: impl(cfg, s, t, f, *c))(state, lanes, faults, *controls)
    assert _observer_equations(off.jaxpr) == 0 < _observer_equations(on.jaxpr)
    assert len(on.out_avals) == len(off.out_avals) + len(TELEMETRY_LANE_SPECS)


if __name__ == "__main__":
    drive()
