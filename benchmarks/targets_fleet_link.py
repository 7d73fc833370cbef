"""A ``TenantFleet`` that takes one-way link faults: gray failure in every
cluster of a fleet.

The target is a ``FleetTarget`` in its ``kind``, its view and its
configuration's ``"deployment": "fleet"`` (the readers that ask for a fleet
see one; ``generators/fleet_link_faults.py`` builds it by name, as the grid's
and the bootstrap's generators build their own). What differs is the detector
it is built with and the traffic it takes. Every tenant runs the paper's
windowed failure detector (the configuration's ``fd_window``, which
``FleetTarget`` does not pass) and the fleet is warmed before anybody copies
it: ``fd_window`` quiet rounds through the fleet's own ``step``, so every
edge's window is full when a step starts. ``inject_links`` hands every
tenant's faulty set, the loss, the schedule and a draw seed a tenant to the
program's ``TenantFleet.set_link_faults`` and waits for the placement
(``sync``); ``restore`` also clears the lane. A program without that seam
cannot run the traffic: the target says so before it builds anything.
"""

from __future__ import annotations

import numpy as np

from benchmarks import targets


class LinkFleetTarget(targets.FleetTarget):
    #: One step's budget, as ``ClusterTarget`` gives its ``until_membership``.
    MAX_STEPS, MAX_CUTS, MIN_CUTS = 192, 4, 1

    def __init__(self, config: dict, seed: int, platform: str):
        from rapid_tpu.tenancy.fleet import TenantFleet

        if not hasattr(TenantFleet, "set_link_faults"):
            raise AttributeError(
                "this program's TenantFleet has no set_link_faults: it cannot run link-fault traffic")
        if config["cohort_assignment"] != "roundrobin":
            raise ValueError(f"unknown cohort_assignment {config['cohort_assignment']!r}")
        if config["fd_stagger_rounds"]:
            raise ValueError("a warmed windowed detector takes no stagger (fd_stagger_rounds 0)")
        tenants = config["tenants"]
        seeds = [int(s) for s in targets.fold_seed(seed, 2).generate_state(tenants, np.uint64)]
        fleet = TenantFleet.create(
            tenants, config["members"], n_slots=config["slots"], k=config["k"],
            cohorts=config["cohorts"], seeds=seeds,
            knobs=[(config["h"], config["l"], config["fd_threshold"])] * tenants,
            fd_window=config["fd_window"], delivery_spread=config["delivery_spread"],
        )
        for _ in range(config["fd_window"]):  # quiet rounds: every window fills with successes
            fleet.step()
        fleet.sync()
        targets._Target.__init__(self, fleet, config, tenants)
        self.low = config["l"]

    def counters(self) -> dict:
        """Adds the lane's counter (minted with the first lane a fleet is given)."""
        kept = self.driver.metrics.counters
        if "engine_link_probes_lost" not in kept:
            return super().counters()
        return dict(super().counters(), link={"probes_lost": int(kept["engine_link_probes_lost"])})

    def observers(self) -> np.ndarray:
        """[tenants, k, slots]: who observes whom on which ring (-1: nobody),
        fetched once at set-up for the traffic's precondition."""
        return np.asarray(self.driver.state.obs_idx)

    def restore(self, pristine) -> None:
        super().restore(pristine)
        self.driver.links = None

    def inject_links(self, pairs, permille: int, on_rounds: int, off_rounds: int, seeds) -> None:
        """``pairs``: [m, 2] (tenant, slot); ``seeds``: [tenants]."""
        self.driver.set_link_faults(
            pairs, permille, on_rounds=on_rounds, off_rounds=off_rounds, seeds=seeds)
        self.driver.sync()

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
            "tenant_rounds": rounds, "tenant_cuts": cuts, "tenant_resolved": resolved,
            "sizes": sizes,
        }
