"""Closed loop, one whole bootstrap at a time: restore, then wave after wave
of joins, each injected and resolved, then the full check.

Every step starts from the same pristine state (a device-resident copy of
the seed clusters) and admits every spare slot of every tenant in the traffic
file's ``waves`` equal join waves; which slot joins in which wave is drawn
per tenant from the seed, once, so every step of a run is the same work. The
commit time of a step is the time to bootstrap: from just before the first
wave's injection to the return of the last wave's driver call. Inside it the
generator only compares what each wave's call fetched (who resolved, through
which sizes) with what the plain reference expects; the pair arrays and those
expectations are made before it. The restore before and the view check after
are inside the window and outside the commit time.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import membership_model, targets
from benchmarks.targets_fleet_join import JoinFleetTarget

NO_CRASH = np.zeros((0, 2), dtype=np.int32)


def join_waves(traffic: dict, target, seed: int) -> list:
    """One [tenants * joiners, 2] (tenant, slot) array per wave: per tenant a
    seeded shuffle of its spare slots, cut into ``waves`` equal parts."""
    waves, spare = int(traffic["waves"]), target.slots - target.members
    if spare <= 0 or spare % waves:
        raise ValueError(f"{spare} spare slots do not make {waves} equal waves")
    rng = np.random.default_rng(targets.fold_seed(seed, 3))
    # the order of `spare` uniform numbers is a uniform shuffle, for all tenants at once
    order = np.argsort(rng.random((target.tenants, spare)), axis=1).astype(np.int32)
    slots = (target.members + order).reshape(target.tenants, waves, spare // waves)
    tenant = np.broadcast_to(
        np.arange(target.tenants, dtype=np.int32)[:, None], slots[:, 0].shape)
    return [
        np.stack([tenant.reshape(-1), slots[:, wave].reshape(-1)], axis=1)
        for wave in range(waves)
    ]


def trail_faults(outcome: dict, start: np.ndarray, goal: np.ndarray) -> tuple:
    """(tenants unresolved, tenants whose sizes are unaccounted) of one wave's
    fetch: a tenant's committed sizes grow strictly from ``start`` and end at
    ``goal``, the reference's membership after the wave, in as many steps as
    it reports cuts; the rest of its row is unused."""
    sizes, cuts = outcome["sizes"], outcome["tenant_cuts"]
    used = np.arange(sizes.shape[1])[None, :] < cuts[:, None]
    steps = np.diff(np.concatenate([start[:, None], sizes], axis=1), axis=1)
    last = np.take_along_axis(sizes, np.maximum(cuts - 1, 0)[:, None], axis=1)[:, 0]
    accounted = (
        ((steps > 0) | ~used).all(axis=1) & ((sizes == -1) | used).all(axis=1)
        & (cuts >= 1) & (last == goal)
    )
    return int((~outcome["tenant_resolved"]).sum()), int((~accounted).sum())


def run(ctx) -> dict:
    traffic = ctx.traffic
    t0 = time.perf_counter()
    target = JoinFleetTarget(ctx.config, ctx.seed, ctx.platform)
    pristine = target.snapshot()
    state_build_s = time.perf_counter() - t0
    waves = join_waves(traffic, target, ctx.seed)
    # The plain reference, ahead of the clock: the membership after each wave
    # and, left in the model, the view a whole bootstrap ends in.
    model = membership_model.MembershipModel(target.initial_alive())
    goals = [model.sizes()]
    for join in waves:
        model.apply(NO_CRASH, join)
        goals.append(model.sizes())
    before = target.view()
    worst = dict.fromkeys(membership_model.LIMITS, 0)
    record = {
        "kind": "bootstrap", "attempted": 0, "failed": 0, "view_changes": 0,
        "rounds": 0, "tenant_rounds_useful": 0, "tenant_rounds_total": 0,
        "commit_ms": [], "commit_parts_ms": [], "commit_rounds": [], "commit_plan": [],
    }

    def step(keep: bool) -> None:
        with ctx.span("restore"):
            target.restore(pristine)
        numbers = {"unresolved": 0, "cut_sizes_unaccounted": 0}
        cuts = np.zeros(target.tenants, dtype=np.int64)
        inject_s = rounds = lockstep = 0
        t_start = time.perf_counter()
        for wave, join in enumerate(waves):
            t_wave = time.perf_counter()
            with ctx.span("inject"):
                target.inject(NO_CRASH, join)
            inject_s += time.perf_counter() - t_wave
            with ctx.span("resolve"):
                outcome = target.resolve(traffic["resolve"], goals[wave + 1])
            unresolved, unaccounted = trail_faults(outcome, goals[wave], goals[wave + 1])
            numbers["unresolved"] += unresolved
            numbers["cut_sizes_unaccounted"] += unaccounted
            cuts += outcome["tenant_cuts"]
            rounds += outcome["rounds"]
            lockstep += outcome["lockstep_rounds"]
        t_done = time.perf_counter()
        with ctx.span("check"):
            view = target.view()
            numbers.update(model.compare_view(view["alive"]))
            numbers.update(model.compare_epochs(before, view))
            # one view sequence: as many view changes as the waves' calls
            # reported cuts, a wave at least and max_cuts a wave at most
            epochs = np.asarray(view["epoch"], dtype=np.int64) - before["epoch"]
            in_range = (cuts >= len(waves)) & (cuts <= len(waves) * target.MAX_CUTS)
            numbers["view_changes_out_of_range"] = int(((epochs != cuts) | ~in_range).sum())
        if not keep:  # the warm-up bootstrap: same path, same check, nothing recorded
            return
        for name, value in numbers.items():
            worst[name] = max(worst[name], value)
        total_s = t_done - t_start
        record["attempted"] += 1
        record["failed"] += int(membership_model.failures(numbers) > 0)
        record["view_changes"] += int(cuts.sum())
        record["rounds"] += lockstep
        record["tenant_rounds_useful"] += rounds
        record["tenant_rounds_total"] += lockstep * target.tenants
        record["commit_ms"].append(total_s * 1e3)
        record["commit_parts_ms"].append((inject_s * 1e3, (total_s - inject_s) * 1e3))
        record["commit_rounds"].append(lockstep)
        record["commit_plan"].append(0)

    step(keep=False)
    with ctx.window(target) as window:
        while window.elapsed() < ctx.seconds:
            step(keep=True)
    record.update(checks=worst, state_build_s=state_build_s)
    return record
