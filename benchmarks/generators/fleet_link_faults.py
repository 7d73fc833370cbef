"""Closed loop over one-way link faults in every cluster of a fleet: restore,
make a set of members faulty in every tenant at once, resolve, check.

Each step starts from the same pristine state (a device-resident copy of a
fleet whose failure detectors are warm, the lane cleared) and gives
``faulty_share`` of every tenant's members a faulty ingress:
``ingress_loss_permille`` of what is sent to them is lost, in the on-phases
of ``on_rounds`` / ``off_rounds``, and they keep sending. The commit time of
a step runs from just before the faults are set
(``TenantFleet.set_link_faults``, every tenant's set in one call, and the
``sync`` that waits for the placement) to the return of the ONE
``TenantFleet.run_until_membership`` that resolves every tenant; the restore
before it and the check after it are inside the window and outside the commit
time. A step's rounds follow its draw (which members of which tenant, and
each probe's outcome), so the draw is fixed as ``link_faults.py`` fixes the
cluster's: the tenants' identities and one cycle of ``plan_cycle`` plans, each
a faulty set and the seed of its probe draws for every tenant, come from the
traffic file's ``arrival_seed``, the run's seed shuffles each cycle, and the
window is whole cycles.

The plain reference is ``membership_model.MembershipModel`` with every
tenant's faulty set as its crashed set: a member the protocol has had time to
detect is out, and nobody else moves. ``link_model.false_reports`` holds the
traffic's precondition, tenant by tenant, at set-up.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import link_model, membership_model, targets
from benchmarks.targets_fleet_link import LinkFleetTarget  # controls patch this name

NO_JOIN = np.zeros((0, 2), dtype=np.int32)


class Plans:
    """One cycle of plans from the traffic file's ``arrival_seed``: per plan
    the [tenants * size, 2] (tenant, slot) pairs, tenant by tenant, and the
    [tenants] seeds of the tenants' probe draws."""

    def __init__(self, traffic: dict, target, seed: int):
        self.size = int(round(target.members * traffic["faulty_share"]))
        fixed = np.random.default_rng(targets.fold_seed(traffic["arrival_seed"], 3))
        observers = target.observers()
        tenant = np.repeat(np.arange(target.tenants, dtype=np.int32), self.size)
        self.redraws, self.plans = 0, []
        for _ in range(int(traffic["plan_cycle"])):
            sets = []
            while len(sets) < target.tenants:
                keys = fixed.random(target.members)
                faulty = np.sort(np.argpartition(keys, self.size)[: self.size]).astype(np.int32)
                # Precondition: a healthy member with L or more of its K
                # observers in the set gets that many false reports, sits
                # between the watermarks and holds its tenant's proposal back.
                reports = link_model.false_reports(observers[len(sets)], faulty)
                reports[faulty] = 0
                if (reports >= target.low).any():
                    self.redraws += 1
                    continue
                sets.append(faulty)
            draw_seeds = fixed.integers(0, 2**32, size=target.tenants, dtype=np.uint64)
            self.plans.append((np.stack([tenant, np.concatenate(sets)], axis=1), draw_seeds))
        self._rng = np.random.default_rng(targets.fold_seed(seed, 3))

    def cycle(self):
        """(plan id, pairs, draw seeds) of one cycle's steps."""
        for plan in self._rng.permutation(len(self.plans)):
            yield (int(plan), *self.plans[plan])


def tenant_faults(members: int, target_members: int, max_cuts: int, outcome: dict, epochs) -> tuple:
    """(tenants whose sizes are unaccounted, tenants out of the range of view
    changes) of one step: every committed size falls strictly from the full
    membership, the last is the reference's, the rest of the row is unused;
    and a tenant changes its view as often as the call reported cuts for it,
    once at least and ``max_cuts`` times at most."""
    sizes, cuts = outcome["sizes"], outcome["tenant_cuts"]
    used = np.arange(sizes.shape[1])[None, :] < cuts[:, None]
    falls = np.diff(sizes, axis=1, prepend=members)
    last = np.take_along_axis(sizes, np.maximum(cuts - 1, 0)[:, None], axis=1)[:, 0]
    accounted = (
        ((falls < 0) | ~used).all(axis=1) & ((sizes == -1) | used).all(axis=1)
        & (cuts >= 1) & (last == target_members)
    )
    in_range = (epochs == cuts) & (cuts >= 1) & (cuts <= max_cuts)
    return int((~accounted).sum()), int((~in_range).sum())


def run(ctx) -> dict:
    traffic = ctx.traffic
    t0 = time.perf_counter()
    # The fixed draw fixes the tenants too: which edges a faulty set has
    # follows the members' places on each tenant's rings.
    target = LinkFleetTarget(ctx.config, traffic["arrival_seed"], ctx.platform)
    pristine = target.snapshot()
    state_build_s = time.perf_counter() - t0
    schedule = Plans(traffic, target, ctx.seed)
    print(f"faulty sets: {len(schedule.plans)} plans of {target.tenants} tenants x {schedule.size} "
          f"members, {schedule.redraws} tenant-draws redrawn for the precondition", flush=True)
    model = membership_model.MembershipModel(target.initial_alive())
    before = target.view()
    target_members = target.members - schedule.size
    permille, on, off = (int(traffic[key]) for key in ("ingress_loss_permille", "on_rounds", "off_rounds"))
    worst = dict.fromkeys(membership_model.LIMITS, 0)
    record = {
        "kind": "fleet_link_faults", "attempted": 0, "failed": 0, "view_changes": 0,
        "rounds": 0, "tenant_rounds_useful": 0, "tenant_rounds_total": 0,
        "commit_ms": [], "commit_parts_ms": [], "commit_rounds": [], "commit_plan": [],
    }
    seen = {}

    def step(plan: int, pairs, draw_seeds, keep: bool) -> None:
        with ctx.span("restore"):
            target.restore(pristine)
        model.reset()
        model.apply(pairs, NO_JOIN)
        t_inject = time.perf_counter()
        with ctx.span("inject"):
            target.inject_links(pairs, permille, on, off, draw_seeds)
        t_resolve = time.perf_counter()
        with ctx.span("resolve"):
            outcome = target.resolve(traffic["resolve"], target_members)
        t_done = time.perf_counter()
        with ctx.span("check"):
            view = target.view()
            numbers = model.compare_view(view["alive"])
            numbers.update(model.compare_epochs(before, view))
            epochs = np.asarray(view["epoch"], dtype=np.int64) - before["epoch"]
            unaccounted, out_of_range = tenant_faults(
                target.members, target_members, target.MAX_CUTS, outcome, epochs)
            numbers["cut_sizes_unaccounted"] = unaccounted
            numbers["view_changes_out_of_range"] = max(numbers["view_changes_out_of_range"], out_of_range)
            numbers["unresolved"] = int((~outcome["tenant_resolved"]).sum())
        if not keep:  # a warm-up step: same path, same check, nothing recorded
            return
        for name, value in numbers.items():
            worst[name] = max(worst[name], value)
        seen.setdefault(plan, (
            outcome["lockstep_rounds"], int(outcome["tenant_rounds"].min()), outcome["cuts"]))
        record["attempted"] += 1
        record["failed"] += int(membership_model.failures(numbers) > 0)
        record["view_changes"] += outcome["cuts"]
        record["rounds"] += outcome["lockstep_rounds"]
        record["tenant_rounds_useful"] += outcome["rounds"]
        record["tenant_rounds_total"] += outcome["lockstep_rounds"] * target.tenants
        record["commit_ms"].append((t_done - t_inject) * 1e3)
        record["commit_parts_ms"].append(((t_resolve - t_inject) * 1e3, (t_done - t_resolve) * 1e3))
        record["commit_rounds"].append(outcome["lockstep_rounds"])
        record["commit_plan"].append(plan)

    for _ in range(2):  # warm-up: two steps through the same path
        step(*next(schedule.cycle()), keep=False)
    with ctx.window(target) as window:
        while window.elapsed() < ctx.seconds:
            for plan, pairs, draw_seeds in schedule.cycle():
                step(plan, pairs, draw_seeds, keep=True)
    print("steps (plan: lockstep rounds / the fastest tenant's / cuts): " + " ".join(
        f"{plan}:{a}/{b}/{c}" for plan, (a, b, c) in sorted(seen.items())), flush=True)
    record.update(checks=worst, state_build_s=state_build_s)
    return record
