"""The serving tier under a trickle: ``StreamDriver`` fed whole cycles of
seeded Poisson waves, closed by its own backpressure.

The window is a whole number of cycles (see ``churn.py``), submitted until
the asked seconds have passed and then drained; its length is what the clock
read from the first submit to the end of the drain. View changes are counted
from the configuration epochs the driver fetches at its drains, never from
what was submitted. The check runs once the window has closed: quiet waves
let the last cuts commit, then the fetched view must equal the model's.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import membership_model, targets
from benchmarks.generators import churn


def _make_churn(traffic: dict, target, seed: int):
    sequence = targets.fold_seed(seed, 4)
    params = traffic[target.kind]
    if target.kind == "cluster":
        return churn.PoissonChurn(
            target.members, target.slots, params["rate_per_wave"],
            params["join_fraction"], params["cycle_waves"], traffic["arrival_seed"], sequence)
    return churn.FleetPoissonChurn(
        target.tenants, target.members, params["rate_per_tenant_wave"],
        params["cycle_waves"], traffic["arrival_seed"], sequence)


def run(ctx) -> dict:
    from rapid_tpu.serving.stream import FleetWave, StreamDriver, StreamWave

    traffic = ctx.traffic
    t0 = time.perf_counter()
    target = ctx.build_target(ctx.seed)
    state_build_s = time.perf_counter() - t0
    source = _make_churn(traffic, target, ctx.seed)
    model = membership_model.MembershipModel(target.initial_alive())
    before = target.view()
    driver = StreamDriver(target.driver, traffic["rounds_per_wave"], traffic["depth"])

    def as_wave(crash, join):
        if target.kind == "fleet":
            return FleetWave(crash=tuple(map(tuple, crash.tolist())))
        return StreamWave(crash=tuple(crash[:, 1].tolist()), join=tuple(join[:, 1].tolist()))

    record = {
        "kind": "stream", "attempted": 0, "wave_ms": [],
        "tenant_rounds_useful": 0, "tenant_rounds_total": 0,
    }

    def cycle(keep: bool) -> None:
        last = time.perf_counter()
        for crash, join in source.cycle():
            model.apply(crash, join)
            with ctx.span("submit"):
                driver.submit(as_wave(crash, join))
            if keep:
                now = time.perf_counter()
                record["wave_ms"].append((now - last) * 1e3)
                last = now
                record["attempted"] += 1
                hit = len(np.unique(np.concatenate([crash[:, 0], join[:, 0]])))
                record["tenant_rounds_useful"] += hit * traffic["rounds_per_wave"]
                record["tenant_rounds_total"] += target.tenants * traffic["rounds_per_wave"]

    cycle(keep=False)
    with ctx.span("drain"):
        warm = driver.drain()
    with ctx.window(target) as window:
        while window.elapsed() < ctx.seconds:
            cycle(keep=True)
        with ctx.span("drain"):
            closed = driver.drain()
    record["view_changes"] = closed.cuts - warm.cuts
    record["rounds"] = closed.rounds - warm.rounds

    # Outside the window: quiet waves (no new program: the same enqueued
    # round) until the last injected events have had a full wave to commit.
    for _ in range(2):
        driver.submit(as_wave(churn.NO_PAIRS, churn.NO_PAIRS))
    settled = driver.drain()
    view = target.view()
    numbers = model.compare_view(view["alive"])
    numbers.update(model.compare_epochs(before, view))
    numbers["unresolved"] = int((view["alive"].sum(axis=1) != model.sizes()).sum())
    # every cut the drains counted is a cut the epochs show, and the reverse
    numbers["cut_sizes_unaccounted"] = abs(
        int((view["epoch"] - before["epoch"]).sum()) - settled.cuts)
    # One check stands for the whole stream: if it fails, no wave is vouched for.
    record["failed"] = record["attempted"] if membership_model.failures(numbers) else 0
    record.update(checks=numbers, state_build_s=state_build_s)
    return record
