"""Closed loop over a one-way partition during a correlated failure: restore,
partition, crash, resolve, check.

Each step starts from the same pristine state (a device-resident copy, whose
receive blocking is empty). ``deaf_zones`` of the configuration's zones stop
hearing one rack of ``rack_share`` of the members, who are healthy and hear
and send as before, and ``crash_share`` of the members crash. The commit time
of a step runs from just before the partition is set to the return of the
driver call whose fetch carries the decision; the restore before it and the
check after it are inside the window and outside the commit time. Which
victims have observers in the rack follows the draw, so the draw is fixed as
``closed_loop.py`` fixes churn5's: the cluster's identities and one cycle of
``plan_cycle`` plans come from the traffic file's ``arrival_seed``, the run's
seed shuffles each cycle, and the window is whole cycles.

Two plain references hold a step: ``membership_model.MembershipModel`` with the
crashed set (the view), and ``consensus_model.outcome`` (which path decides
which cut in how many attempts), derived once a plan at set-up from the
schedule and the observer table, against the program's three counters of the
consensus path.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import consensus_model, membership_model, targets, targets_partition  # noqa: F401  (registers the deployment)

NO_JOIN = np.zeros((0, 2), dtype=np.int32)


class Plans:
    """One cycle of plans (a crashed set and an unheard rack, disjoint), from
    the traffic file's ``arrival_seed``, each with what the plain reference
    expects of it."""

    def __init__(self, traffic: dict, target, seed: int):
        self.n_crash = int(round(target.members * traffic["crash_share"]))
        self.n_rack = int(round(target.members * traffic["rack_share"]))
        self.deaf = target.cohorts_of_zones(int(traffic["deaf_zones"]))
        fixed = np.random.default_rng(targets.fold_seed(traffic["arrival_seed"], 3))
        observers, cohort_of, knobs = target.observers(), target.cohort_of(), target.knobs()
        deaf_mask = np.isin(np.arange(target.cohorts), self.deaf)
        alive = target.initial_alive()[0]
        self.plans = []
        for _ in range(int(traffic["plan_cycle"])):
            order = np.argsort(fixed.random(target.members))
            crash = np.sort(order[: self.n_crash]).astype(np.int32)
            rack = np.sort(order[self.n_crash: self.n_crash + self.n_rack]).astype(np.int32)
            expected = consensus_model.outcome(
                members=target.members, alive=alive, cohort_of=cohort_of,
                crashed=np.isin(np.arange(target.slots), crash), deaf=deaf_mask,
                unheard=np.isin(np.arange(target.slots), rack), observers=observers, **knobs)
            if (expected["path"], expected["cuts"], expected["attempts"]) != ("classic", 1, 1):
                raise ValueError(
                    f"plan {len(self.plans)} is not this traffic: the plain reference expects "
                    f"{expected['path']} / {expected['cuts']} cut(s) / {expected['attempts']} attempt(s)")
            self.plans.append((crash, rack, expected))
        self._rng = np.random.default_rng(targets.fold_seed(seed, 3))

    def cycle(self):
        """(plan id, crashed slots, rack slots, expected outcome) of one cycle's steps."""
        for plan in self._rng.permutation(len(self.plans)):
            yield (int(plan), *self.plans[plan])


def run(ctx) -> dict:
    traffic = ctx.traffic
    t0 = time.perf_counter()
    # The fixed draw fixes the cluster too: which victims have observers in
    # the rack follows the members' places on this cluster's rings.
    target = ctx.build_target(traffic["arrival_seed"])
    pristine = target.snapshot()
    state_build_s = time.perf_counter() - t0
    schedule = Plans(traffic, target, ctx.seed)
    votes = sorted(expected["votes"] for _, _, expected in schedule.plans)
    print(f"plans: {len(schedule.plans)}, {schedule.n_crash} crashed and {schedule.n_rack} unheard by "
          f"{len(schedule.deaf)} cohorts; the plain reference expects the classic path in every one "
          f"({votes[0]}-{votes[-1]} fast votes against a quorum of "
          f"{schedule.plans[0][2]['quorum']})", flush=True)
    model = membership_model.MembershipModel(target.initial_alive())
    before = target.view()
    target_members = target.members - schedule.n_crash
    worst = dict.fromkeys(membership_model.LIMITS, 0)
    record = {
        "kind": "partition", "attempted": 0, "failed": 0, "view_changes": 0,
        "rounds": 0, "tenant_rounds_useful": 0, "tenant_rounds_total": 0,
        "commit_ms": [], "commit_parts_ms": [], "commit_rounds": [], "commit_plan": [],
    }

    def step(plan: int, crash, rack, expected: dict, keep: bool) -> None:
        with ctx.span("restore"):
            target.restore(pristine)
        model.reset()
        model.apply(np.stack([np.zeros_like(crash), crash], axis=1), NO_JOIN)
        paths_before = target.paths() or dict.fromkeys(targets_partition.PATH_COUNTERS, 0)
        t_inject = time.perf_counter()
        with ctx.span("inject"):
            target.inject_partition(schedule.deaf, rack, crash)
        t_resolve = time.perf_counter()
        with ctx.span("resolve"):
            outcome = target.resolve(traffic["resolve"], target_members)
        t_done = time.perf_counter()
        with ctx.span("check"):
            view = target.view()
            numbers = model.compare_view(view["alive"])
            numbers.update(model.compare_epochs(before, view))
            # one view sequence, and the path the plain reference expects:
            # one cut, as many view changes as cuts, decided by the classic
            # round in the attempts the reference counts, never by the fast one
            epochs = int(view["epoch"][0]) - int(before["epoch"][0])
            cuts = outcome["cuts"]
            paths_after = target.paths() or paths_before
            moved = {name: paths_after[name] - paths_before[name] for name in paths_before}
            wanted = {
                "classic_rounds": expected["attempts"],
                "classic_decisions": int(expected["path"] == "classic"),
                "fast_decisions": int(expected["path"] == "fast"),
            }
            if epochs != cuts or cuts != expected["cuts"] or moved != wanted:
                numbers["view_changes_out_of_range"] = 1
            # liveness: the cut commits though no fast quorum exists
            numbers["unresolved"] = int(not outcome["resolved"])
            numbers["cut_sizes_unaccounted"] = int(outcome["sizes"] != [int(model.sizes()[0])])
        if not keep:  # a warm-up step: same path, same check, nothing recorded
            return
        for name, value in numbers.items():
            worst[name] = max(worst[name], value)
        record["attempted"] += 1
        record["failed"] += int(membership_model.failures(numbers) > 0)
        record["view_changes"] += cuts
        record["rounds"] += outcome["lockstep_rounds"]
        record["tenant_rounds_useful"] += outcome["rounds"]
        record["tenant_rounds_total"] += outcome["lockstep_rounds"]
        record["commit_ms"].append((t_done - t_inject) * 1e3)
        record["commit_parts_ms"].append(((t_resolve - t_inject) * 1e3, (t_done - t_resolve) * 1e3))
        record["commit_rounds"].append(outcome["lockstep_rounds"])
        record["commit_plan"].append(plan)

    for _ in range(2):  # warm-up: two steps through the same path
        step(*next(schedule.cycle()), keep=False)
    with ctx.window(target) as window:
        while window.elapsed() < ctx.seconds:
            for plan, crash, rack, expected in schedule.cycle():
                step(plan, crash, rack, expected, keep=True)
    record.update(checks=worst, state_build_s=state_build_s)
    return record
