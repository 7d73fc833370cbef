"""One ``VirtualCluster`` that takes one-way link faults, as a deployment kind.

Importing this module adds ``cluster_link`` to ``targets.TARGETS``. The target
is a ``ClusterTarget`` in its counters, its view and its resolve, and keeps
``kind = "cluster"``; what differs is the detector it is built with and the
traffic it takes. The cluster runs the paper's windowed failure detector
(the configuration's ``fd_window``, which ``ClusterTarget`` does not pass) and
is warmed before anybody copies it: ``fd_window`` quiet rounds through the
driver's own ``step``, so every edge's window is full when a step starts.
``inject_links`` hands a faulty set, its loss and its schedule to the
program's ``VirtualCluster.set_link_faults`` and waits for the scatter
(``sync``). A program without that seam cannot run the traffic: the target
says so before it builds anything.
"""

from __future__ import annotations

import numpy as np

from benchmarks import targets


class LinkClusterTarget(targets.ClusterTarget):
    #: One step's budget, as ``ClusterTarget`` gives its ``until_membership``.
    MAX_STEPS, MAX_CUTS, MIN_CUTS = 192, 4, 1

    def __init__(self, config: dict, seed: int, platform: str):
        from rapid_tpu.models.virtual_cluster import VirtualCluster

        if not hasattr(VirtualCluster, "set_link_faults"):
            raise AttributeError(
                "this program's VirtualCluster has no set_link_faults: it cannot run link-fault traffic")
        identity_seed = int(targets.fold_seed(seed, 1).generate_state(1, np.uint64)[0])
        vc = VirtualCluster.create(
            config["members"], n_slots=config["slots"], k=config["k"],
            h=config["h"], l=config["l"], cohorts=config["cohorts"],
            fd_threshold=config["fd_threshold"], fd_window=config["fd_window"],
            seed=identity_seed,
            use_pallas=bool(config["use_pallas"]) and platform == "tpu",
            delivery_spread=config["delivery_spread"],
            concurrent_coordinators=config["concurrent_coordinators"],
            pallas_lanes=config["pallas_lanes"],
        )
        if config["cohort_assignment"] != "roundrobin":
            raise ValueError(f"unknown cohort_assignment {config['cohort_assignment']!r}")
        if config["fd_stagger_rounds"]:
            raise ValueError("a warmed windowed detector takes no stagger (fd_stagger_rounds 0)")
        vc.assign_cohorts_roundrobin()
        for _ in range(config["fd_window"]):  # quiet rounds: every window fills with successes
            vc.step()
        vc.sync()
        targets._Target.__init__(self, vc, config, tenants=1)

    def counters(self) -> dict:
        """Adds the lane's counter where the program keeps one (it is minted
        with the first lane a cluster is given)."""
        kept = self.driver.metrics.counters
        if "engine_link_probes_lost" not in kept:
            return super().counters()
        return dict(super().counters(), link={"probes_lost": int(kept["engine_link_probes_lost"])})

    def observers(self) -> np.ndarray:
        """[k, slots]: who observes whom on which ring (-1: nobody), fetched
        once at set-up for the traffic's precondition."""
        return np.asarray(self.driver.state.obs_idx)

    def watermark_l(self) -> int:
        return int(self.driver.cfg.l)

    def snapshot(self):
        state, faults = super().snapshot()
        return state, faults, self.driver.links

    def restore(self, pristine) -> None:
        state, faults, links = pristine
        super().restore((state, faults))
        self.driver.links = links

    def inject_links(self, slots, permille: int, on_rounds: int, off_rounds: int, seed: int) -> None:
        self.driver.set_link_faults(
            slots, permille, on_rounds=on_rounds, off_rounds=off_rounds, seed=seed)
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


targets.TARGETS["cluster_link"] = LinkClusterTarget
