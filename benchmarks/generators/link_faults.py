"""Closed loop over one-way link faults: restore, make a set of members
faulty, resolve, check.

Each step starts from the same pristine state (a device-resident copy of a
cluster whose failure detector is warm) and gives ``faulty_share`` of the
members a faulty ingress: ``ingress_loss_permille`` of what is sent to them is
lost, in the on-phases of ``on_rounds`` / ``off_rounds``, and they keep
sending. The commit time of a step runs from just before the faults are set to
the return of the driver call whose fetch carries the decision; the restore
before it and the check after it are inside the window and outside the commit
time. A step's rounds follow its draw (which members, and each probe's
outcome), so the draw is fixed as ``closed_loop.py`` fixes churn5's: the
cluster's identities, one cycle of ``plan_cycle`` faulty sets and the seed of
each set's probe draws come from the traffic file's ``arrival_seed``, the run's
seed shuffles each cycle, and the window is whole cycles.

The plain reference is ``membership_model.MembershipModel`` with the faulty set
as its crashed set: a member the protocol has had time to detect is out, and
nobody else moves.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import link_model, membership_model, targets, targets_link  # noqa: F401  (registers the deployment)

NO_JOIN = np.zeros((0, 2), dtype=np.int32)


class FaultySets:
    """One cycle of faulty sets, each with the seed of its probe draws, from
    the traffic file's ``arrival_seed``."""

    def __init__(self, traffic: dict, target, seed: int):
        self.size = int(round(target.members * traffic["faulty_share"]))
        fixed = np.random.default_rng(targets.fold_seed(traffic["arrival_seed"], 3))
        observers, low = target.observers(), target.watermark_l()
        self.redraws = 0
        self.plans = []
        while len(self.plans) < int(traffic["plan_cycle"]):
            keys = fixed.random(target.members)
            faulty = np.sort(np.argpartition(keys, self.size)[: self.size]).astype(np.int32)
            draw_seed = int(fixed.integers(0, 2**32))
            # Precondition: a healthy member with L or more of its K observers
            # in the set gets that many false reports, sits between the
            # watermarks and holds every proposal back.
            reports = link_model.false_reports(observers, faulty)
            reports[faulty] = 0
            if (reports >= low).any():
                self.redraws += 1
                continue
            self.plans.append((faulty, draw_seed))
        self._rng = np.random.default_rng(targets.fold_seed(seed, 3))

    def cycle(self):
        """(plan id, faulty slots, seed of the probe draws) of one cycle's steps."""
        for plan in self._rng.permutation(len(self.plans)):
            yield (int(plan), *self.plans[plan])


def run(ctx) -> dict:
    traffic = ctx.traffic
    t0 = time.perf_counter()
    # The fixed draw fixes the cluster too: which edges a faulty set has
    # follows the members' places on this cluster's rings.
    target = ctx.build_target(traffic["arrival_seed"])
    pristine = target.snapshot()
    state_build_s = time.perf_counter() - t0
    schedule = FaultySets(traffic, target, ctx.seed)
    print(f"faulty sets: {len(schedule.plans)} of {schedule.size} members, "
          f"{schedule.redraws} redrawn for the precondition", flush=True)
    model = membership_model.MembershipModel(target.initial_alive())
    before = target.view()
    target_members = target.members - schedule.size
    permille, on, off = (int(traffic[key]) for key in ("ingress_loss_permille", "on_rounds", "off_rounds"))
    worst = dict.fromkeys(membership_model.LIMITS, 0)
    record = {
        "kind": "link_faults", "attempted": 0, "failed": 0, "view_changes": 0,
        "rounds": 0, "tenant_rounds_useful": 0, "tenant_rounds_total": 0,
        "commit_ms": [], "commit_parts_ms": [], "commit_rounds": [], "commit_plan": [],
    }

    def step(plan: int, faulty, draw_seed: int, keep: bool) -> None:
        with ctx.span("restore"):
            target.restore(pristine)
        model.reset()
        model.apply(np.stack([np.zeros_like(faulty), faulty], axis=1), NO_JOIN)
        t_inject = time.perf_counter()
        with ctx.span("inject"):
            target.inject_links(faulty, permille, on, off, draw_seed)
        t_resolve = time.perf_counter()
        with ctx.span("resolve"):
            outcome = target.resolve(traffic["resolve"], target_members)
        t_done = time.perf_counter()
        with ctx.span("check"):
            view = target.view()
            numbers = model.compare_view(view["alive"])
            numbers.update(model.compare_epochs(before, view))
            # one view sequence: as many view changes as the call reported
            # cuts, one at least and max_cuts at most
            epochs = int(view["epoch"][0]) - int(before["epoch"][0])
            cuts = outcome["cuts"]
            if epochs != cuts or not 1 <= cuts <= target.MAX_CUTS:
                numbers["view_changes_out_of_range"] = 1
            numbers["unresolved"] = int(not outcome["resolved"])
            # the committed sizes fall from the start to the reference's
            # membership, so the cuts' sizes add up to the faulty set
            sizes = [target.members, *outcome["sizes"]]
            falling = all(a > b for a, b in zip(sizes, sizes[1:]))
            numbers["cut_sizes_unaccounted"] = int(
                not falling or len(sizes) != cuts + 1 or sizes[-1] != model.sizes()[0])
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
            for plan, faulty, draw_seed in schedule.cycle():
                step(plan, faulty, draw_seed, keep=True)
    record.update(checks=worst, state_build_s=state_build_s)
    return record
