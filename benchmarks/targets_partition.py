"""One ``VirtualCluster`` that takes a one-way partition, as a deployment kind.

Importing this module adds ``cluster_partition`` to ``targets.TARGETS``. The
target is a ``ClusterTarget`` in its view, its restore and its transfer
counters, and keeps ``kind = "cluster"`` (``control.py``'s crash faults patch
it as they patch churn5's); what differs is that it is built with the
configuration's ``fallback_rounds``, knows which cohorts sit in which zone, and
takes a partition with its crashes: ``inject_partition`` hands the deaf cohorts
and the unheard senders to the program's ``VirtualCluster.set_partition``, the
victims to ``crash``, and waits for the scatters (``sync``). A program without
that seam cannot run the traffic: the target says so before it builds anything.
"""

from __future__ import annotations

import numpy as np

from benchmarks import targets

#: The program's counters of the consensus path, under the names the readers
#: and the check use.
PATH_COUNTERS = {
    "classic_rounds": "engine_classic_rounds",
    "classic_decisions": "engine_classic_decisions",
    "fast_decisions": "engine_fast_decisions",
}


class PartitionClusterTarget(targets.ClusterTarget):
    #: One step's budget, as ``ClusterTarget`` gives its ``until_membership``.
    MAX_STEPS, MAX_CUTS, MIN_CUTS = 192, 4, 1

    def __init__(self, config: dict, seed: int, platform: str):
        from rapid_tpu.models.virtual_cluster import VirtualCluster

        if not hasattr(VirtualCluster, "set_partition"):
            raise AttributeError(
                "this program's VirtualCluster has no set_partition: it cannot run partition traffic")
        identity_seed, stagger_seed = (
            int(s) for s in targets.fold_seed(seed, 1).generate_state(2, np.uint64))
        vc = VirtualCluster.create(
            config["members"], n_slots=config["slots"], k=config["k"],
            h=config["h"], l=config["l"], cohorts=config["cohorts"],
            fd_threshold=config["fd_threshold"], seed=identity_seed,
            use_pallas=bool(config["use_pallas"]) and platform == "tpu",
            fallback_rounds=config["fallback_rounds"],
            delivery_spread=config["delivery_spread"],
            concurrent_coordinators=config["concurrent_coordinators"],
            pallas_lanes=config["pallas_lanes"],
        )
        if config["cohort_assignment"] != "roundrobin":
            raise ValueError(f"unknown cohort_assignment {config['cohort_assignment']!r}")
        if config["cohorts"] % config["zones"]:
            raise ValueError("the zones have to hold the same number of cohorts each")
        vc.assign_cohorts_roundrobin()
        if config["fd_stagger_rounds"]:
            vc.stagger_fd_counts(np.random.default_rng(stagger_seed), config["fd_stagger_rounds"])
        vc.sync()
        targets._Target.__init__(self, vc, config, tenants=1)
        self.cohorts, self.zones = config["cohorts"], config["zones"]

    def paths(self):
        """The program's three counters of the consensus path as they stand,
        or ``None`` where it keeps none (they are minted with the first
        partition a cluster is given)."""
        kept = self.driver.metrics.counters
        if any(name not in kept for name in PATH_COUNTERS.values()):
            return None
        return {short: int(kept[name]) for short, name in PATH_COUNTERS.items()}

    def counters(self) -> dict:
        paths = self.paths()
        return super().counters() if paths is None else dict(super().counters(), consensus=paths)

    # -- what the plain reference is handed, once, at set-up -----------------

    def observers(self) -> np.ndarray:
        """[k, slots]: who observes whom on which ring (-1: nobody)."""
        return np.asarray(self.driver.state.obs_idx)

    def cohort_of(self) -> np.ndarray:
        """[slots]: round-robin, as the constructor assigned them."""
        return np.arange(self.slots, dtype=np.int32) % self.cohorts

    def cohorts_of_zones(self, zones: int) -> np.ndarray:
        """The cohorts of the first ``zones`` zones (cohort c is in zone
        c // (cohorts / zones))."""
        return np.arange(zones * (self.cohorts // self.zones), dtype=np.int32)

    def knobs(self) -> dict:
        cfg = self.driver.cfg
        return {"high": int(cfg.h), "low": int(cfg.l), "fallback_rounds": int(cfg.fallback_rounds)}

    # -- a step ----------------------------------------------------------------

    def inject_partition(self, cohorts, senders, crash) -> None:
        self.driver.set_partition(cohorts, senders)
        self.driver.crash(crash)
        self.driver.sync()

    def resolve(self, mode: str, target_members: int) -> dict:
        if mode != "until_membership":
            raise ValueError(f"unknown resolve mode {mode!r}")
        rounds, cuts, resolved, sizes = self.driver.run_until_membership(
            target_members, max_steps=self.MAX_STEPS, max_cuts=self.MAX_CUTS,
            min_cuts=self.MIN_CUTS,
        )
        return {
            "rounds": rounds, "lockstep_rounds": rounds, "cuts": cuts,
            "resolved": bool(resolved), "sizes": list(sizes),
        }


targets.TARGETS["cluster_partition"] = PartitionClusterTarget
