"""Closed loop over the paper's K/H/L sensitivity grid as one fleet: restore,
crash F(t) members in every tenant at once, resolve, check.

Tenant t holds combination ``t // repetitions`` of the configuration's grid:
its own watermarks (H, L) and its own number F of concurrent crashes. Each
step starts from the same pristine state (a device-resident copy). The commit
time of a step runs from just before the crashes are injected
(``TenantFleet.stream_crash``, every tenant's victims in one call) to the
return of the ONE ``TenantFleet.run_until_membership`` that resolves every
tenant to its own target; the restore before it and, after it, the ``sync``,
the read of the telemetry lanes and the check are inside the window and
outside the commit time. Which cohort of which tenant announces which cut
follows the victims' places on the rings and the network's delays, so the
draw is fixed as ``closed_loop.py`` fixes churn5's: the tenants' identities
and one cycle of ``plan_cycle`` victim draws come from the traffic file's
``arrival_seed``, the run's seed shuffles each cycle, and the window is whole
cycles.

Three plain references hold a step, tenant by tenant and exactly:
``membership_model.MembershipModel`` with the crashed sets (the view), and
``detector_model.expectation`` over ``consensus_model``'s quorums (which
cohort announces which cut in which round, hence which path decides which cut
in which round, and how many cohorts had announced another), derived once a
plan at set-up from the schedule, the observer tables and the delays, against
the wave's own fetch and the differences of the tenants' telemetry lanes.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import detector_model, membership_model, targets, targets_fleet_grid
from benchmarks.targets_fleet_grid import GridFleetTarget  # controls patch this name

NO_JOIN = np.zeros((0, 2), dtype=np.int32)
PATHS = ("fast", "classic")  # the first two of targets_fleet_grid.ACTIVITY_LANES


class Plan:
    """One draw of victims for every tenant, and what the plain reference
    expects of every tenant under it, as [tenants] arrays (-1: the reference
    names none)."""

    def __init__(self, config: dict, target, triples: np.ndarray, victims: list):
        observers, cohort_of = target.observers(), target.cohort_of()
        tenants = np.repeat(np.arange(target.tenants, dtype=np.int32), triples[:, 2])
        self.crash = np.stack([tenants, np.concatenate(victims)], axis=1)
        self.members = target.members
        self.expected = [
            detector_model.expectation(
                members=target.members, observers=observers[t], cohort_of=cohort_of,
                victims=victims[t], delays=delays, high=int(high), low=int(low),
                fd_threshold=config["fd_threshold"], fallback_rounds=config["fallback_rounds"])
            for t, ((high, low, _), delays) in enumerate(zip(triples, target.delays(victims)))
        ]
        for t, expected in enumerate(self.expected):
            if expected["path"] == "none":
                raise ValueError(
                    f"tenant {t} (H, L, F = {triples[t].tolist()}): the plain reference "
                    f"finds no decision, so this draw is not this traffic")
        self.path = np.asarray([PATHS.index(e["path"]) for e in self.expected])
        self.whole = np.asarray([e["whole"] for e in self.expected])
        self.rounds = np.asarray([e["round"] + 1 for e in self.expected])
        self.dissent = np.asarray([-1 if e["dissent"] is None else e["dissent"] for e in self.expected])
        self.first_size = np.asarray([
            -1 if e["cut"] is None else target.members - int(e["cut"].sum()) for e in self.expected])


class Plans:
    """One cycle of plans from the traffic file's ``arrival_seed``."""

    def __init__(self, config: dict, traffic: dict, target, seed: int):
        triples = targets_fleet_grid.grid(config)
        self.crashes = triples[:, 2]
        fixed = np.random.default_rng(targets.fold_seed(traffic["arrival_seed"], 3))
        self.plans = [
            Plan(config, target, triples,
                 [np.sort(fixed.choice(target.members, size=f, replace=False)).astype(np.int32)
                  for f in self.crashes])
            for _ in range(int(traffic["plan_cycle"]))
        ]
        if not any((plan.path == PATHS.index("classic")).any() for plan in self.plans):
            raise ValueError(
                "no plan has a tenant that the plain reference expects on the classic path: "
                "this is not this traffic")
        self._rng = np.random.default_rng(targets.fold_seed(seed, 3))

    def cycle(self):
        """(plan id, plan) of one cycle's steps."""
        for plan in self._rng.permutation(len(self.plans)):
            yield int(plan), self.plans[plan]


def tenant_faults(plan: Plan, target_members, outcome: dict, epochs, moved) -> tuple:
    """(tenants whose sizes are unaccounted, tenants off the reference's path)
    of one step. ``moved``: [tenants, 3], the step's differences of the
    tenants' lanes (``targets_fleet_grid.ACTIVITY_LANES``)."""
    sizes, cuts = outcome["sizes"], outcome["tenant_cuts"]
    # every committed size falls strictly from the full membership, the last
    # is the target, the rest of the row is unused; the first is the
    # reference's where it names a cut
    used = np.arange(sizes.shape[1])[None, :] < cuts[:, None]
    falls = np.diff(sizes, axis=1, prepend=plan.members)
    last = np.take_along_axis(sizes, np.maximum(cuts - 1, 0)[:, None], axis=1)[:, 0]
    accounted = (
        ((falls < 0) | ~used).all(axis=1) & ((sizes == -1) | used).all(axis=1)
        & (cuts >= 1) & (last == target_members)
        & ((plan.first_size < 0) | (sizes[:, 0] == plan.first_size))
    )
    # the path: one decision a cut; a tenant the reference resolves in one
    # view change takes exactly that one, by the reference's path, in its
    # round, with its count of dissenting cohorts; a tenant whose first cut
    # is partial decides that one by the reference's path and goes on
    decisions = moved[:, :2]
    by_path = np.take_along_axis(decisions, plan.path[:, None], axis=1)[:, 0]
    on_path = (epochs == cuts) & (decisions.sum(axis=1) == cuts) & np.where(
        plan.whole,
        (cuts == 1) & (by_path == 1) & (outcome["tenant_rounds"] == plan.rounds)
        & (moved[:, 2] == plan.dissent),
        (by_path >= 1) & (moved[:, 2] >= plan.dissent) & ((cuts >= 2) | (plan.first_size < 0)),
    )
    return int((~accounted).sum()), int((~on_path).sum())


def run(ctx) -> dict:
    config, traffic = ctx.config, ctx.traffic
    t0 = time.perf_counter()
    # The fixed draw fixes the tenants too: which cohort hears which report
    # when follows the members' places on each tenant's rings.
    target = GridFleetTarget(config, traffic["arrival_seed"], ctx.platform)
    pristine = target.snapshot()
    state_build_s = time.perf_counter() - t0
    schedule = Plans(config, traffic, target, ctx.seed)
    target_members = target.members - schedule.crashes
    for number, plan in enumerate(schedule.plans):
        classic = plan.path == PATHS.index("classic")
        print(f"plan {number}: the plain reference expects {int(classic.sum())} of {target.tenants} "
              f"tenants on the classic path, {int((~plan.whole).sum())} with a partial first cut, "
              f"{int(np.maximum(plan.dissent, 0).sum())} dissenting cohorts, decisions in rounds "
              f"{int(plan.rounds.min())}-{int(plan.rounds.max())}", flush=True)
    model = membership_model.MembershipModel(target.initial_alive())
    before = target.view()
    lanes = target.activity()  # as the last step left them
    worst = dict.fromkeys(membership_model.LIMITS, 0)
    record = {
        "kind": "grid", "attempted": 0, "failed": 0, "view_changes": 0,
        "rounds": 0, "tenant_rounds_useful": 0, "tenant_rounds_total": 0,
        "tenant_steps": 0, "tenant_steps_classic": 0, "dissent": 0,
        "commit_ms": [], "commit_parts_ms": [], "commit_rounds": [], "commit_plan": [],
    }
    seen = {}

    def step(number: int, plan: Plan, keep: bool) -> None:
        nonlocal lanes
        with ctx.span("restore"):
            target.restore(pristine)
        model.reset()
        model.apply(plan.crash, NO_JOIN)
        t_inject = time.perf_counter()
        with ctx.span("inject"):
            target.inject(plan.crash, NO_JOIN)
        t_resolve = time.perf_counter()
        with ctx.span("resolve"):
            outcome = target.resolve(traffic["resolve"], target_members)
        t_done = time.perf_counter()
        with ctx.span("check"):
            view, now = target.view(), target.activity()
            moved, lanes = now - lanes, now
            numbers = model.compare_view(view["alive"])
            numbers.update(model.compare_epochs(before, view))
            epochs = np.asarray(view["epoch"], dtype=np.int64) - before["epoch"]
            unaccounted, off_path = tenant_faults(plan, target_members, outcome, epochs, moved)
            numbers["cut_sizes_unaccounted"] = unaccounted
            numbers["view_changes_out_of_range"] = max(numbers["view_changes_out_of_range"], off_path)
            numbers["unresolved"] = int((~outcome["tenant_resolved"]).sum())
        if not keep:  # a warm-up step: same path, same check, nothing recorded
            return
        for name, value in numbers.items():
            worst[name] = max(worst[name], value)
        seen.setdefault(number, (
            outcome["lockstep_rounds"], int(outcome["tenant_rounds"].min()),
            int(moved[:, 1].sum()), int(moved[:, 2].sum())))
        record["attempted"] += 1
        record["failed"] += int(membership_model.failures(numbers) > 0)
        record["view_changes"] += outcome["cuts"]
        record["rounds"] += outcome["lockstep_rounds"]
        record["tenant_rounds_useful"] += outcome["rounds"]
        record["tenant_rounds_total"] += outcome["lockstep_rounds"] * target.tenants
        record["tenant_steps"] += target.tenants
        record["tenant_steps_classic"] += int(moved[:, 1].sum())
        record["dissent"] += int(moved[:, 2].sum())
        record["commit_ms"].append((t_done - t_inject) * 1e3)
        record["commit_parts_ms"].append(((t_resolve - t_inject) * 1e3, (t_done - t_resolve) * 1e3))
        record["commit_rounds"].append(outcome["lockstep_rounds"])
        record["commit_plan"].append(number)

    for _ in range(2):  # warm-up: two steps through the same path
        step(*next(schedule.cycle()), keep=False)
    with ctx.window(target) as window:
        while window.elapsed() < ctx.seconds:
            for number, plan in schedule.cycle():
                step(number, plan, keep=True)
    print("steps (plan: lockstep rounds / the fastest tenant's / classic decisions / dissenting "
          "cohorts): " + " ".join(
              f"{number}:{a}/{b}/{c}/{d}" for number, (a, b, c, d) in sorted(seen.items())), flush=True)
    record.update(checks=worst, state_build_s=state_build_s)
    return record
