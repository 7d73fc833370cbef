"""Closed loop, one step at a time: restore, inject, resolve, check.

Each step starts from the same pristine state (a device-resident copy), so
every step of a run is the same amount of work; the victims are drawn from
the seed. Where a step's time depends on which members it draws, the traffic
file fixes the draw (``plan_cycle``): the cluster's identities and one cycle
of victim sets come from the file's ``arrival_seed``, the run's seed only
shuffles each cycle, and the window is whole cycles. Every seed then offers
the same set of steps in another order. The commit time of a step runs from just before its faults are
injected to the return of the driver call whose fetch carries the decision.
The restore before it and the check after it are inside the window and
outside the commit time.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import membership_model, targets


class StepSchedule:
    """The victims and joiners of every step, from the seed (or, with
    ``plan_cycle``, from the traffic file's ``arrival_seed``)."""

    def __init__(self, traffic: dict, target, seed: int):
        self._tenants, self._members = target.tenants, target.members
        if "crashes_per_cluster" in traffic:
            self.n_crash, self.n_join = int(traffic["crashes_per_cluster"]), 0
        else:
            self.n_crash = int(round(target.members * traffic["crash_share"]))
            self.n_join = int(round(target.members * traffic["join_share"]))
        if self.n_join > target.slots - target.members:
            raise ValueError("join_share needs more spare slots than the configuration has")
        self._tenant_col = np.repeat(np.arange(self._tenants, dtype=np.int32), self.n_crash)
        joiners = np.arange(target.members, target.members + self.n_join, dtype=np.int32)
        self.join = np.stack([np.zeros_like(joiners), joiners], axis=1)
        self._rng = np.random.default_rng(targets.fold_seed(seed, 3))
        self.cycle_len = int(traffic.get("plan_cycle", 1))
        self._plans = None
        if "plan_cycle" in traffic:
            fixed = np.random.default_rng(targets.fold_seed(traffic["arrival_seed"], 3))
            self._plans = [self._draw(fixed) for _ in range(self.cycle_len)]

    def _draw(self, rng) -> np.ndarray:
        # One draw for all tenants: the n_crash smallest of members uniform
        # numbers are a uniform sample without replacement.
        keys = rng.random((self._tenants, self._members))
        victims = np.argpartition(keys, self.n_crash, axis=1)[:, : self.n_crash]
        return np.stack([self._tenant_col, victims.reshape(-1).astype(np.int32)], axis=1)

    def cycle(self):
        """(plan id, crash pairs, join pairs) of one cycle's steps."""
        if self._plans is None:
            yield 0, self._draw(self._rng), self.join
            return
        for plan in self._rng.permutation(self.cycle_len):
            yield int(plan), self._plans[plan], self.join


def run(ctx) -> dict:
    traffic = ctx.traffic
    t0 = time.perf_counter()
    # A fixed draw fixes the cluster too: a step's time follows the victims'
    # places on this cluster's rings, not their slot numbers.
    target = ctx.build_target(traffic["arrival_seed"] if "plan_cycle" in traffic else ctx.seed)
    pristine = target.snapshot()
    state_build_s = time.perf_counter() - t0
    schedule = StepSchedule(traffic, target, ctx.seed)
    model = membership_model.MembershipModel(target.initial_alive())
    before = target.view()
    target_members = target.members - schedule.n_crash + schedule.n_join
    worst = dict.fromkeys(membership_model.LIMITS, 0)
    record = {
        "kind": "closed_loop", "attempted": 0, "failed": 0, "view_changes": 0,
        "rounds": 0, "tenant_rounds_useful": 0, "tenant_rounds_total": 0,
        "commit_ms": [], "commit_parts_ms": [], "commit_rounds": [], "commit_plan": [],
    }

    def step(plan: int, crash, join, keep: bool) -> None:
        with ctx.span("restore"):
            target.restore(pristine)
        model.reset()
        model.apply(crash, join)
        t_inject = time.perf_counter()
        with ctx.span("inject"):
            target.inject(crash, join)
        t_resolve = time.perf_counter()
        with ctx.span("resolve"):
            outcome = target.resolve(traffic["resolve"], target_members)
        t_done = time.perf_counter()
        with ctx.span("check"):
            view = target.view()
            numbers = model.compare_view(view["alive"])
            numbers.update(model.compare_epochs(before, view))
            numbers["unresolved"] = int(not outcome["resolved"])
            numbers["cut_sizes_unaccounted"] = int(
                (np.asarray(outcome["final_sizes"]) != model.sizes()).sum()
            )
        if not keep:  # a warm-up step: same path, same check, nothing recorded
            return
        for name, value in numbers.items():
            worst[name] = max(worst[name], value)
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
            for plan, crash, join in schedule.cycle():
                step(plan, crash, join, keep=True)
    record.update(checks=worst, state_build_s=state_build_s)
    return record
