"""A ``TenantFleet`` whose tenants hold different watermarks: the paper's
K/H/L sensitivity grid as one fleet.

The target is a ``FleetTarget`` in its ``kind``, its view, its restore and
its configuration's ``"deployment": "fleet"`` (the readers that ask for a
fleet see one; ``generators/grid.py`` builds it by name, as the bootstrap's
generator builds its own). What differs: tenant t is built with the (H, L) of
combination ``t // repetitions`` of the configuration's grid, with the
configuration's ``fallback_rounds`` and with the telemetry plane on; it
resolves a step through the fleet's whole-wave loop to per-tenant targets;
and it hands the plain reference what that is owed as data, once, at set-up:
the observer tables, the cohort assignment and the delivery delay of every
(cohort, victim, ring) edge (``TenantFleet.delivery_delays``). A program
without that accessor, or without the telemetry lane that counts dissenting
cohorts, cannot run this deployment: the target says so before it builds
anything.
"""

from __future__ import annotations

import itertools

import numpy as np

from benchmarks import targets
from benchmarks.targets_fleet_join import FLEET_COUNTERS

#: The lanes of a tenant's activity the check takes differences of.
ACTIVITY_LANES = ("decisions_fast", "decisions_classic", "dissent")


def grid(config: dict) -> np.ndarray:
    """[tenants, 3]: the (H, L, F) of every tenant. The combinations run H
    outermost and F innermost; tenant t holds combination t // repetitions."""
    combinations = list(itertools.product(
        config["h_values"], config["l_values"], config["f_values"]))
    triples = np.repeat(np.asarray(combinations, dtype=np.int32), config["repetitions"], axis=0)
    if len(triples) != config["tenants"]:
        raise ValueError(
            f"{len(combinations)} combinations x {config['repetitions']} repetitions "
            f"are not the configuration's {config['tenants']} tenants")
    return triples


class GridFleetTarget(targets.FleetTarget):
    #: One step's budget, as ``ClusterTarget`` gives its ``until_membership``.
    MAX_STEPS, MAX_CUTS, MIN_CUTS = 192, 4, 1

    def __init__(self, config: dict, seed: int, platform: str):
        from rapid_tpu.models.state import TELEMETRY_LANE_SPECS
        from rapid_tpu.tenancy.fleet import TenantFleet

        if not hasattr(TenantFleet, "delivery_delays"):
            raise AttributeError(
                "this program's TenantFleet has no delivery_delays: the plain reference "
                "cannot be handed the network's schedule")
        if "tl_dissent" not in TELEMETRY_LANE_SPECS:
            raise AttributeError(
                "this program's telemetry plane has no tl_dissent lane: it cannot count "
                "the cohorts that announced another cut than the decided one")
        if config["cohort_assignment"] != "roundrobin":
            raise ValueError(f"unknown cohort_assignment {config['cohort_assignment']!r}")
        tenants = config["tenants"]
        seeds = [int(s) for s in targets.fold_seed(seed, 2).generate_state(tenants, np.uint64)]
        fleet = TenantFleet.create(
            tenants, config["members"], n_slots=config["slots"], k=config["k"],
            cohorts=config["cohorts"], seeds=seeds,
            knobs=[(int(h), int(l), config["fd_threshold"]) for h, l, _ in grid(config)],
            delivery_spread=config["delivery_spread"],
            fallback_rounds=config["fallback_rounds"], telemetry=config["telemetry"],
        )
        fleet.sync()
        targets._Target.__init__(self, fleet, config, tenants)
        self.cohorts = config["cohorts"]

    def counters(self) -> dict:
        kept = self.driver.metrics.counters
        return dict(
            super().counters(),
            fleet={name: int(kept[name]) for name in FLEET_COUNTERS if name in kept},
        )

    # -- what the plain reference is handed, once, at set-up -----------------

    def observers(self) -> np.ndarray:
        """[tenants, k, slots]: who observes whom on which ring (-1: nobody)."""
        return np.asarray(self.driver.state.obs_idx)

    def cohort_of(self) -> np.ndarray:
        """[slots]: round-robin, as the constructor assigned them."""
        return np.arange(self.slots, dtype=np.int32) % self.cohorts

    def delays(self, victims: list) -> list:
        """Per tenant [cohorts, len(victims[t]), k]: the rounds from the
        firing of each of those members' edges to its arrival at each cohort,
        in the configuration the tenant is in now."""
        return self.driver.delivery_delays(victims)

    # -- a step ----------------------------------------------------------------

    def resolve(self, mode: str, target_members) -> dict:
        """``target_members``: [tenants]."""
        if mode != "until_membership":
            raise ValueError(f"unknown resolve mode {mode!r}")
        rounds, cuts, resolved, sizes = self.driver.run_until_membership(
            np.asarray(target_members), max_steps=self.MAX_STEPS,
            max_cuts=self.MAX_CUTS, min_cuts=self.MIN_CUTS,
        )
        return {
            "rounds": int(rounds.sum()), "lockstep_rounds": int(rounds.max()),
            "cuts": int(cuts.sum()), "resolved": bool(resolved.all()),
            "tenant_rounds": rounds, "tenant_cuts": cuts, "tenant_resolved": resolved,
            "sizes": sizes,
        }

    def activity(self) -> np.ndarray:
        """[tenants, 3] (``ACTIVITY_LANES``): every tenant's counts as they
        stand, through the program's own boundary (``sync`` fetches the
        digest; ``tenant_activity`` reads what it fetched)."""
        self.driver.sync()
        return np.asarray(
            [[tenant[lane] for lane in ACTIVITY_LANES] for tenant in self.driver.tenant_activity],
            dtype=np.int64)
