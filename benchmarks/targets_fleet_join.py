"""A ``TenantFleet`` that takes joins: the bootstrap's deployment.

The target is a ``FleetTarget`` in its construction, its ``kind`` and its
configuration's ``"deployment": "fleet"`` (the readers that ask for a fleet
see one); what differs is the traffic it takes. ``inject`` hands join pairs
to the program's ``TenantFleet.inject_join_wave``, ``resolve`` runs the
fleet's whole-wave loop to per-tenant target memberships
(``TenantFleet.run_until_membership``), and ``counters`` adds the fleet's
own round and cut counters. A program without the join seam cannot run the
traffic and fails at its first injection.
"""

from __future__ import annotations

import numpy as np

from benchmarks import targets

#: Counters of the fleet's ``Metrics`` that the readers of this deployment's
#: cells take differences of; one the program does not keep is left out.
FLEET_COUNTERS = (
    "engine_tenant_cuts", "engine_fleet_wave_rounds", "engine_fleet_commit_rounds",
    "engine_fleet_invalidation_rounds", "engine_fleet_classic_rounds",
)


def window_counts(run, *names):
    """The window's differences of the named fleet counters, or ``None``
    where the program does not keep one of them (the parent of the PR that
    brought it): the result line then leaves the metric out."""
    before = run["counters_before"].get("fleet", {})
    after = run["counters_after"].get("fleet", {})
    if any(name not in after for name in names):
        return None
    return [after[name] - before.get(name, 0) for name in names]


class JoinFleetTarget(targets.FleetTarget):
    #: One wave's budget, as ``ClusterTarget`` gives its ``until_membership``.
    MAX_STEPS, MAX_CUTS, MIN_CUTS = 192, 4, 1

    def counters(self) -> dict:
        kept = self.driver.metrics.counters
        return dict(
            super().counters(),
            fleet={name: int(kept[name]) for name in FLEET_COUNTERS if name in kept},
        )

    def inject(self, crash, join) -> None:
        """``join``: [m, 2] (tenant, slot) pairs, any number a tenant."""
        if len(crash):
            raise ValueError("this deployment's traffic is joins only")
        self.driver.inject_join_wave(join, check_admissible=True)

    def resolve(self, mode: str, target_members) -> dict:
        """``target_members``: one membership for every tenant, or [tenants]."""
        if mode != "until_membership":
            raise ValueError(f"unknown resolve mode {mode!r}")
        rounds, cuts, resolved, sizes = self.driver.run_until_membership(
            np.asarray(target_members), max_steps=self.MAX_STEPS,
            max_cuts=self.MAX_CUTS, min_cuts=self.MIN_CUTS,
        )
        return {
            "rounds": int(rounds.sum()), "lockstep_rounds": int(rounds.max()),
            "cuts": int(cuts.sum()), "resolved": bool(resolved.all()),
            "tenant_cuts": cuts, "tenant_resolved": resolved, "sizes": sizes,
        }
