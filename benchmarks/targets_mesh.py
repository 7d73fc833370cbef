"""One ``VirtualCluster`` sharded over a device mesh, as a deployment kind.

Importing this module adds ``cluster_mesh`` to ``targets.TARGETS``. The
target is a ``ClusterTarget`` in every verb (inject, resolve, view, restore)
and keeps ``kind = "cluster"``, so the generators and the controls drive it
unchanged; only the construction differs: the configuration's ``mesh`` names
the axes and their sizes, and the cluster is built on it by the program's own
``VirtualCluster.create(..., mesh=...)``. A program without that argument
cannot run the configuration and fails at once.
"""

from __future__ import annotations

import numpy as np

from benchmarks import targets


class MeshClusterTarget(targets.ClusterTarget):
    def __init__(self, config: dict, seed: int, platform: str):
        import jax

        from rapid_tpu.models.virtual_cluster import VirtualCluster
        from rapid_tpu.parallel.mesh import COHORT_AXIS, NODE_AXIS, make_mesh

        shape = tuple(config["mesh"]["shape"])
        if config["mesh"]["axes"] != [COHORT_AXIS, NODE_AXIS]:
            raise ValueError(f"unknown mesh axes {config['mesh']['axes']!r}")
        if config["use_pallas"]:
            raise ValueError("the delivery kernel does not run under a mesh")
        devices = jax.devices()[: int(np.prod(shape))]
        identity_seed = int(targets.fold_seed(seed, 1).generate_state(1, np.uint64)[0])
        vc = VirtualCluster.create(
            config["members"], n_slots=config["slots"], k=config["k"],
            h=config["h"], l=config["l"], cohorts=config["cohorts"],
            fd_threshold=config["fd_threshold"], seed=identity_seed,
            use_pallas=False, delivery_spread=config["delivery_spread"],
            concurrent_coordinators=config["concurrent_coordinators"],
            mesh=make_mesh(devices, shape=shape),
        )
        if config["cohort_assignment"] != "roundrobin":
            raise ValueError(f"unknown cohort_assignment {config['cohort_assignment']!r}")
        vc.assign_cohorts_roundrobin()
        vc.sync()
        targets._Target.__init__(self, vc, config, tenants=1)


targets.TARGETS["cluster_mesh"] = MeshClusterTarget
