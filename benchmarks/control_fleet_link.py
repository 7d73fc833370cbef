"""The fleet's link-fault controls: a run of the gray-failure cell with one
stated guarantee broken underneath, as ``control_link.py`` breaks the
cluster's cells' (whose faults patch ``VirtualCluster.set_link_faults``'s
slot list; a fleet's injection takes (tenant, slot) pairs).

``python3 benchmarks/control_fleet_link.py --fault <name> --workload ...
--seed ... --seconds ... --trace 0`` drives the same harness over the same
system, with the driver's link-fault injection altered where the benchmark
cannot see it. The run has to end with ``correct: false``.

- ``lose_fault``: every injection drops each tenant's last faulty member, so
  in every tenant a member whose ingress the schedule made faulty stays in the
  view ("exactly the faulty set is removed").
- ``deafen_healthy``: every injection also names, in each tenant, a member the
  schedule never did, so every tenant evicts a healthy member ("no healthy
  member is evicted").

Either way no tenant's membership reaches the step's target, so a control's
steps run their whole round budget and are long: give it a short window. The
benchmark's own runs never come through here.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np


def _lose_fault(target):
    inject = target.driver.set_link_faults

    def broken(pairs, *args, **kw):
        pairs = np.asarray(pairs, dtype=np.int32).reshape(-1, 2)
        # the first of a tenant's pairs, seen from the end, is its last
        _, from_end = np.unique(pairs[::-1, 0], return_index=True)
        return inject(np.delete(pairs, len(pairs) - 1 - from_end, axis=0), *args, **kw)

    target.driver.set_link_faults = broken


def _deafen_healthy(target):
    inject, calls = target.driver.set_link_faults, [0]

    def broken(pairs, *args, **kw):
        pairs = np.asarray(pairs, dtype=np.int32).reshape(-1, 2)
        calls[0] += 1
        extra = []
        for tenant in np.unique(pairs[:, 0]):
            taken = set(pairs[pairs[:, 0] == tenant, 1].tolist())
            slot = (calls[0] * 9973 + int(tenant) * 31) % target.members
            while slot in taken:
                slot = (slot + 1) % target.members
            extra.append((tenant, slot))
        return inject(np.concatenate([pairs, np.asarray(extra, dtype=np.int32)]), *args, **kw)

    target.driver.set_link_faults = broken


FAULTS = {"lose_fault": _lose_fault, "deafen_healthy": _deafen_healthy}


def main(argv, t_process_start) -> int:
    from benchmarks import harness
    from benchmarks.generators import fleet_link_faults

    if "--fault" not in argv or argv[argv.index("--fault") + 1] not in FAULTS:
        raise SystemExit(f"benchmarks/control_fleet_link.py needs --fault, one of {sorted(FAULTS)}")
    at = argv.index("--fault")
    name, rest = argv[at + 1], argv[:at] + argv[at + 2:]
    build = fleet_link_faults.LinkFleetTarget

    def broken_build(config, seed, platform):
        target = build(config, seed, platform)
        FAULTS[name](target)
        return target

    fleet_link_faults.LinkFleetTarget = broken_build
    print(f"control: fault {name} installed under the driver", flush=True)
    return harness.main(rest, t_process_start)


if __name__ == "__main__":
    T_PROCESS_START = time.perf_counter()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks import control_fleet_link

    sys.exit(control_fleet_link.main(sys.argv[1:], T_PROCESS_START))
